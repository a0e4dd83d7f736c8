"""ctypes binding to the native dmlctpu runtime (libdmlctpu.so).

The native library provides the Stream/InputSplit/Parser/RecordIO substrate
(reference parity: include/dmlc + src of /root/reference, rebuilt TPU-first
in cpp/).  This module only loads the shared object and declares signatures;
pythonic wrappers live in `dmlc_core_tpu.io` and `dmlc_core_tpu.data`.

Resolution order for the library path:
  1. $DMLCTPU_LIBRARY_PATH
  2. <repo>/build/libdmlctpu.so — built with cmake+ninja when it is missing
     or older than cpp/ (``build/`` is not committed, so a checkout and a
     copied working tree must not end up running different libraries)
  3. alongside this package (wheel layout)
"""
from __future__ import annotations

import ctypes
import os
import subprocess
import time
from pathlib import Path

_REPO_ROOT = Path(__file__).resolve().parent.parent
_REPO_SO = _REPO_ROOT / "build" / "libdmlctpu.so"


class RowBlockC(ctypes.Structure):
    """Mirror of DmlcTpuRowBlockC (cpp/include/dmlctpu/c_api.h)."""

    _fields_ = [
        ("size", ctypes.c_uint64),
        ("offset", ctypes.POINTER(ctypes.c_uint64)),
        ("label", ctypes.POINTER(ctypes.c_float)),
        ("weight", ctypes.POINTER(ctypes.c_float)),
        ("qid", ctypes.POINTER(ctypes.c_uint64)),
        ("field", ctypes.POINTER(ctypes.c_uint64)),
        ("index", ctypes.POINTER(ctypes.c_uint64)),
        ("value", ctypes.POINTER(ctypes.c_float)),
    ]


def _lock_handle():
    """Open (creating if needed) the cross-process build lock file.

    Serializes the gate (scripts/check.sh), bench device children, and
    pytest workers: two concurrent `cmake -B` configures of one tree corrupt
    each other's CMakeFiles/, and dlopen of a .so that ninja is relinking in
    place raises invalid-ELF.  Builders take LOCK_EX, loaders LOCK_SH."""
    build_dir = _REPO_ROOT / "build"
    build_dir.mkdir(parents=True, exist_ok=True)
    return open(build_dir / ".dmlctpu_build_lock", "w")


def _build_direct(build_dir: Path, so: Path) -> None:
    """cmake-less fallback: one g++ invocation over every .cc (containers
    that ship only a bare toolchain still get a working runtime)."""
    import shutil
    cxx = shutil.which("g++") or shutil.which("c++") or shutil.which("clang++")
    if cxx is None:
        raise RuntimeError("native build failed: no cmake and no C++ "
                           "compiler (g++/c++/clang++) on PATH")
    sources = sorted(
        str(p) for sub in ("cpp/src", "cpp/src/io", "cpp/src/data")
        for p in (_REPO_ROOT / sub).glob("*.cc"))
    cmd = [cxx, "-O3", "-g", "-std=c++20", "-fPIC", "-shared", "-pthread",
           "-fvisibility-inlines-hidden", "-I", str(_REPO_ROOT / "cpp/include"),
           *sources, "-o", str(so)]
    proc = subprocess.run(cmd, cwd=_REPO_ROOT, capture_output=True, text=True)
    if proc.returncode != 0:
        raise RuntimeError(f"native build failed ({cxx}, "
                           f"rc={proc.returncode}):\n{proc.stderr[-2000:]}")


def _repo_so_current() -> bool:
    """build/libdmlctpu.so exists and no native source is newer than it."""
    if not _REPO_SO.exists():
        return False
    built = _REPO_SO.stat().st_mtime
    sources = [_REPO_ROOT / "CMakeLists.txt"]
    for sub in ("cpp/src", "cpp/include"):
        sources += (p for p in (_REPO_ROOT / sub).rglob("*") if p.is_file())
    return all(p.stat().st_mtime <= built for p in sources)


def _build_native() -> float | None:
    """Bring build/libdmlctpu.so up to date with cpp/ (an incremental ninja
    build: only what changed recompiles); returns the seconds it took, None
    when another process got there first."""
    import fcntl
    import shutil
    with _lock_handle() as lock:
        fcntl.flock(lock, fcntl.LOCK_EX)
        if _repo_so_current():  # another process built it while we waited
            return None
        t0 = time.monotonic()
        build_dir = _REPO_SO.parent
        if shutil.which("cmake") is None or shutil.which("ninja") is None:
            _build_direct(build_dir, _REPO_SO)
        else:
            for cmd in (["cmake", "-B", str(build_dir), "-G", "Ninja",
                         "-DCMAKE_BUILD_TYPE=Release"],
                        ["ninja", "-C", str(build_dir), "dmlctpu"]):
                proc = subprocess.run(cmd, cwd=_REPO_ROOT,
                                      capture_output=True, text=True)
                if proc.returncode != 0:
                    # surface the compiler/linker output: an opaque import
                    # failure here makes EVERY Python entry point
                    # undiagnosable
                    raise RuntimeError(
                        f"native build failed ({' '.join(cmd[:2])}, "
                        f"rc={proc.returncode}):\n{proc.stderr[-2000:]}")
        # a source ninja had no reason to rebuild for (a touched header
        # nothing includes) leaves the old mtime: stamp the library so the
        # next import does not come back here
        os.utime(_REPO_SO)
        return time.monotonic() - t0


def _load():
    """-> (CDLL, path, build seconds or None when nothing was built)."""
    import fcntl
    env = os.environ.get("DMLCTPU_LIBRARY_PATH")
    wheel_so = Path(__file__).resolve().parent / "libdmlctpu.so"
    in_checkout = (_REPO_ROOT / "CMakeLists.txt").exists()
    build_seconds = None
    if env and Path(env).exists():
        path = Path(env)
    elif in_checkout:
        path = _REPO_SO
        if not _repo_so_current():
            build_seconds = _build_native()
    elif wheel_so.exists():
        path = wheel_so
    else:
        raise OSError("libdmlctpu.so not found: set DMLCTPU_LIBRARY_PATH or "
                      "import from a source checkout (which builds it)")
    # Shared lock around dlopen: a concurrent rebuild relinks the .so
    # non-atomically, and CDLL on the half-written file fails with an
    # invalid-ELF OSError.  Taken only after _build_native released its
    # exclusive lock (flock via a second fd in the same process would
    # otherwise self-deadlock).
    with _lock_handle() as lock:
        fcntl.flock(lock, fcntl.LOCK_SH)
        return ctypes.CDLL(str(path)), path, build_seconds


_LIB, _LIB_PATH, _BUILD_SECONDS = _load()

# ---- signatures -------------------------------------------------------------
_LIB.DmlcTpuGetLastError.argtypes = []
_LIB.DmlcTpuGetLastError.restype = ctypes.c_char_p
_LIB.DmlcTpuVersion.argtypes = []
_LIB.DmlcTpuVersion.restype = ctypes.c_char_p

_LIB.DmlcTpuParserCreate.argtypes = [
    ctypes.c_char_p, ctypes.c_uint, ctypes.c_uint, ctypes.c_char_p,
    ctypes.POINTER(ctypes.c_void_p)]
_LIB.DmlcTpuParserCreateEx.argtypes = [
    ctypes.c_char_p, ctypes.c_uint, ctypes.c_uint, ctypes.c_char_p,
    ctypes.c_int, ctypes.c_int, ctypes.c_uint64,
    ctypes.POINTER(ctypes.c_void_p)]
_LIB.DmlcTpuSetDefaultParseThreads.argtypes = [ctypes.c_int]
_LIB.DmlcTpuGetDefaultParseThreads.argtypes = [ctypes.POINTER(ctypes.c_int)]
_LIB.DmlcTpuParserNext.argtypes = [ctypes.c_void_p, ctypes.POINTER(RowBlockC)]
_LIB.DmlcTpuParserBeforeFirst.argtypes = [ctypes.c_void_p]
_LIB.DmlcTpuParserBytesRead.argtypes = [ctypes.c_void_p]
_LIB.DmlcTpuParserBytesRead.restype = ctypes.c_int64
_LIB.DmlcTpuParserSetPoolKnobs.argtypes = [
    ctypes.c_void_p, ctypes.c_int, ctypes.c_uint64, ctypes.c_uint64,
    ctypes.POINTER(ctypes.c_int)]
_LIB.DmlcTpuParserFree.argtypes = [ctypes.c_void_p]
_LIB.DmlcTpuParserFree.restype = None

_LIB.DmlcTpuInputSplitCreate.argtypes = [
    ctypes.c_char_p, ctypes.c_char_p, ctypes.c_uint, ctypes.c_uint, ctypes.c_char_p,
    ctypes.c_int, ctypes.c_int, ctypes.c_uint64, ctypes.POINTER(ctypes.c_void_p)]
_LIB.DmlcTpuInputSplitNextRecord.argtypes = [
    ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_uint64)]
_LIB.DmlcTpuInputSplitNextChunk.argtypes = list(_LIB.DmlcTpuInputSplitNextRecord.argtypes)
_LIB.DmlcTpuInputSplitBeforeFirst.argtypes = [ctypes.c_void_p]
_LIB.DmlcTpuInputSplitResetPartition.argtypes = [
    ctypes.c_void_p, ctypes.c_uint, ctypes.c_uint]
_LIB.DmlcTpuInputSplitTotalSize.argtypes = [ctypes.c_void_p]
_LIB.DmlcTpuInputSplitTotalSize.restype = ctypes.c_int64
_LIB.DmlcTpuInputSplitFree.argtypes = [ctypes.c_void_p]
_LIB.DmlcTpuInputSplitFree.restype = None

_LIB.DmlcTpuRecordIOWriterCreate.argtypes = [ctypes.c_char_p, ctypes.POINTER(ctypes.c_void_p)]
_LIB.DmlcTpuRecordIOWriterWrite.argtypes = [ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64]
_LIB.DmlcTpuRecordIOWriterClose.argtypes = [ctypes.c_void_p]
_LIB.DmlcTpuRecordIOWriterFree.argtypes = [ctypes.c_void_p]
_LIB.DmlcTpuRecordIOWriterFree.restype = None
_LIB.DmlcTpuRecordIOReaderCreate.argtypes = [ctypes.c_char_p, ctypes.POINTER(ctypes.c_void_p)]
_LIB.DmlcTpuRecordIOReaderCreateEx.argtypes = [
    ctypes.c_char_p, ctypes.c_int, ctypes.POINTER(ctypes.c_void_p)]
_LIB.DmlcTpuRecordIOReaderNext.argtypes = [
    ctypes.c_void_p, ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_uint64)]
_LIB.DmlcTpuRecordIOReaderCorruptSkipped.argtypes = [ctypes.c_void_p]
_LIB.DmlcTpuRecordIOReaderCorruptSkipped.restype = ctypes.c_int64
_LIB.DmlcTpuRecordIOReaderFree.argtypes = [ctypes.c_void_p]
_LIB.DmlcTpuRecordIOReaderFree.restype = None

_LIB.DmlcTpuStreamCreate.argtypes = [
    ctypes.c_char_p, ctypes.c_char_p, ctypes.POINTER(ctypes.c_void_p)]
_LIB.DmlcTpuStreamRead.argtypes = [
    ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint64]
_LIB.DmlcTpuStreamRead.restype = ctypes.c_int64
_LIB.DmlcTpuStreamWrite.argtypes = [
    ctypes.c_void_p, ctypes.c_char_p, ctypes.c_uint64]
_LIB.DmlcTpuStreamClose.argtypes = [ctypes.c_void_p]
_LIB.DmlcTpuStreamFree.argtypes = [ctypes.c_void_p]
_LIB.DmlcTpuStreamFree.restype = None
_LIB.DmlcTpuSeekStreamCreate.argtypes = [
    ctypes.c_char_p, ctypes.POINTER(ctypes.c_void_p)]
_LIB.DmlcTpuStreamSeek.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
_LIB.DmlcTpuStreamTell.argtypes = [ctypes.c_void_p]
_LIB.DmlcTpuStreamTell.restype = ctypes.c_int64
_LIB.DmlcTpuFsListDirectory.argtypes = [
    ctypes.c_char_p, ctypes.c_int, ctypes.POINTER(ctypes.c_char_p)]
_LIB.DmlcTpuFsPathInfo.argtypes = [
    ctypes.c_char_p, ctypes.POINTER(ctypes.c_char_p)]

_LIB.DmlcTpuTelemetryEnabled.argtypes = [ctypes.POINTER(ctypes.c_int)]
_LIB.DmlcTpuTelemetrySnapshotJson.argtypes = [ctypes.POINTER(ctypes.c_char_p)]
_LIB.DmlcTpuTelemetryReset.argtypes = []
_LIB.DmlcTpuTelemetryCounterAdd.argtypes = [ctypes.c_char_p, ctypes.c_int64]
_LIB.DmlcTpuTelemetryCounterGet.argtypes = [
    ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64)]
_LIB.DmlcTpuTelemetryTraceStart.argtypes = []
_LIB.DmlcTpuTelemetryTraceStop.argtypes = []
_LIB.DmlcTpuTelemetryTraceDumpJson.argtypes = [ctypes.POINTER(ctypes.c_char_p)]
# the two older forms of RecordSpanTotal: the binding calls neither, and
# `scripts/analyze.py capi` wants every symbol of c_api.h declared here
_LIB.DmlcTpuTelemetryRecordSpan.argtypes = [
    ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64]
_LIB.DmlcTpuTelemetryRecordSpanLineage.argtypes = [
    ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64]
_LIB.DmlcTpuTelemetryRecordSpanTotal.argtypes = [
    ctypes.c_char_p, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
    ctypes.c_char_p, ctypes.c_int]
_LIB.DmlcTpuTelemetryGaugeSet.argtypes = [ctypes.c_char_p, ctypes.c_int64]
_LIB.DmlcTpuTelemetryGaugeAdd.argtypes = [ctypes.c_char_p, ctypes.c_int64]
_LIB.DmlcTpuTelemetryGaugeGet.argtypes = [
    ctypes.c_char_p, ctypes.POINTER(ctypes.c_int64)]
_LIB.DmlcTpuTelemetrySetTraceContext.argtypes = [
    ctypes.c_uint64, ctypes.c_uint64, ctypes.c_int64]
_LIB.DmlcTpuTelemetryGetTraceContext.argtypes = [
    ctypes.POINTER(ctypes.c_uint64), ctypes.POINTER(ctypes.c_uint64),
    ctypes.POINTER(ctypes.c_int64)]
_LIB.DmlcTpuJsonValidate.argtypes = [
    ctypes.c_char_p, ctypes.POINTER(ctypes.c_int)]
_LIB.DmlcTpuWatchdogStart.argtypes = [
    ctypes.c_int64, ctypes.c_int64, ctypes.c_int, ctypes.c_char_p]
_LIB.DmlcTpuWatchdogStop.argtypes = []
_LIB.DmlcTpuWatchdogRunning.argtypes = [ctypes.POINTER(ctypes.c_int)]
_LIB.DmlcTpuWatchdogStallCount.argtypes = [ctypes.POINTER(ctypes.c_int64)]
_LIB.DmlcTpuFlightRecordJson.argtypes = [
    ctypes.c_char_p, ctypes.POINTER(ctypes.c_char_p)]
_LIB.DmlcTpuWatchdogLastRecordJson.argtypes = [
    ctypes.POINTER(ctypes.c_char_p)]
_LIB.DmlcTpuTimeseriesStart.argtypes = [
    ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64]
_LIB.DmlcTpuTimeseriesStop.argtypes = []
_LIB.DmlcTpuTimeseriesActive.argtypes = [ctypes.POINTER(ctypes.c_int)]
_LIB.DmlcTpuTimeseriesSample.argtypes = []
_LIB.DmlcTpuTimeseriesJson.argtypes = [ctypes.POINTER(ctypes.c_char_p)]
_LIB.DmlcTpuTimeseriesTailJson.argtypes = [
    ctypes.c_int, ctypes.POINTER(ctypes.c_char_p)]

_LIB.DmlcTpuFaultCompiledIn.argtypes = [ctypes.POINTER(ctypes.c_int)]
_LIB.DmlcTpuFaultArm.argtypes = [ctypes.c_char_p]
_LIB.DmlcTpuFaultDisarm.argtypes = []
_LIB.DmlcTpuFaultSnapshotJson.argtypes = [ctypes.POINTER(ctypes.c_char_p)]
_LIB.DmlcTpuFaultInjectedTotal.argtypes = [ctypes.POINTER(ctypes.c_int64)]

LOG_CALLBACK_TYPE = ctypes.CFUNCTYPE(
    None, ctypes.c_int, ctypes.c_char_p, ctypes.c_char_p)
_LIB.DmlcTpuLogSetCallback.argtypes = [LOG_CALLBACK_TYPE]
_LIB.DmlcTpuLogEmit.argtypes = [ctypes.c_int, ctypes.c_char_p]


class NativeError(RuntimeError):
    """Error raised by the native dmlctpu runtime."""


def check(status: int) -> int:
    """Raise NativeError on -1; pass through 0/1 returns."""
    if status == -1:
        raise NativeError(_LIB.DmlcTpuGetLastError().decode(errors="replace"))
    return status


def lib() -> ctypes.CDLL:
    return _LIB


def version() -> str:
    return _LIB.DmlcTpuVersion().decode()


def build_info() -> dict:
    """Which library this process loaded, and whether importing the package
    had to (re)build it: ``{"library", "version", "built", "build_seconds"}``."""
    return {"library": str(_LIB_PATH), "version": version(),
            "built": _BUILD_SECONDS is not None,
            "build_seconds": round(_BUILD_SECONDS or 0.0, 2)}


def set_default_parse_threads(nthread: int) -> None:
    """Pin the parse-thread pool size for parsers created without an
    explicit ``?nthread=`` URI arg; 0 restores the per-parser heuristic."""
    check(_LIB.DmlcTpuSetDefaultParseThreads(int(nthread)))


def get_default_parse_threads() -> int:
    out = ctypes.c_int()
    check(_LIB.DmlcTpuGetDefaultParseThreads(ctypes.byref(out)))
    return out.value


# Keeps the installed ctypes callback alive: native worker threads call it
# long after set_log_callback returns, and GC'ing the CFUNCTYPE wrapper while
# the native side holds its address is a use-after-free.
_log_callback_keepalive = None


def set_log_callback(fn) -> None:
    """Install ``fn(severity:int, where:str, message:str)`` as the process-wide
    log sink (replacing the default stderr sink), or restore stderr with
    ``None``.  Called from arbitrary native threads; ctypes acquires the GIL
    around the callback, so ``fn`` must not block on locks a logging thread
    might hold."""
    global _log_callback_keepalive
    if fn is None:
        null_cb = ctypes.cast(None, LOG_CALLBACK_TYPE)
        check(_LIB.DmlcTpuLogSetCallback(null_cb))
        _log_callback_keepalive = None
        return

    def _trampoline(severity, where, message):
        try:
            fn(int(severity),
               (where or b"").decode(errors="replace"),
               (message or b"").decode(errors="replace"))
        except Exception:
            pass  # a raising sink must never take down a native worker

    cb = LOG_CALLBACK_TYPE(_trampoline)
    check(_LIB.DmlcTpuLogSetCallback(cb))
    _log_callback_keepalive = cb  # replace AFTER install: old cb may be live


def log_emit(severity: int, message: str) -> None:
    """Send one message through the native logging pipeline (0=DEBUG 1=INFO
    2=WARNING 3=ERROR; honors DMLCTPU_LOG_LEVEL)."""
    check(_LIB.DmlcTpuLogEmit(int(severity), str(message).encode()))
