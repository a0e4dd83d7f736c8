"""Binned epoch cache — parse text once, stream int8 bins forever.

Repeat-epoch training pays the full text-parse tax every epoch even though
the GBDT trainer ultimately consumes uint8 bin ids.  This module closes the
loop (ROADMAP item 4): the FIRST epoch runs the sharded parser, feeds
``QuantileBinner.partial_fit_sparse`` to finalize cuts, and writes a
quantized columnar cache — uint8 bin ids + CSR row pointers + labels /
weights, packed per virtual part into RecordIO block records behind a
self-describing header (binner config, cuts digest, parser config, part
map; format spec in doc/binned_cache.md).  Every LATER epoch streams the
cache straight into the staging feed through :class:`BinnedStagingIter`,
bypassing text parse and binning entirely.

Layering: the native side (cpp/src/data/binned_cache.h, via the
DmlcTpuBinnedCache* C API) owns framing, crash-consistent header patching
(DiskRowIter's sentinel discipline), per-part seeks, recover-mode resync,
and the per-entry binning of the build pass (bit-identical to
``QuantileBinner.transform_entries``).  This module owns block payload
packing/unpacking, content-level invalidation (meta digest comparison), the
build orchestration, and the epoch-serving repack into static-shape
:class:`BinnedBatch` pytrees.

Invalidation is by header digest: any change to num_bins / sketch seed or
size / parser config / source byte length / cuts digest triggers exactly
one counted rebuild (``cache.rebuilds``) instead of silently serving stale
bins.  A build cut short mid-write (crash, ENOSPC, the ``cache.write.short``
fault point) leaves a cache the reader rejects; the build is retried once
and, failing that, the epoch degrades to the text-parse path with a
bit-identical batch stream.
"""
from __future__ import annotations

import base64
import ctypes
import hashlib
import json
import logging
import os
import sys
import threading
import time
import weakref
from collections import deque
from dataclasses import dataclass
from typing import Iterator, Optional, Union

import numpy as np

import jax
import jax.numpy as jnp

from .._native import check, lib, NativeError
from .. import telemetry
from .staging import (DeviceStagingIter, _StagedBatchOwnedC,
                      _device_put_maybe_donated, _observability_scope,
                      _pick_virtual_parts, _replicated_sharding, _staged_iter,
                      csr_row_ids)

LOGGER = logging.getLogger("dmlc_core_tpu.binned_cache")

#: bump when the block payload layout or meta contract changes; mismatched
#: caches rebuild rather than misparse
CACHE_META_VERSION = 1

# block payload prefix — mirrors BinnedBlockHeader (binned_cache.h); native
# byte order on both sides, with meta["byte_order"] guarding foreign opens.
# cflag (ex-pad0, always 0 pre-codec) names the block codec; every decoded
# payload handed to unpack_block has it cleared back to 0.
_HDR_DTYPE = np.dtype([("part_id", np.uint32), ("seq", np.uint32),
                       ("num_rows", np.uint64), ("nnz", np.uint64),
                       ("flags", np.uint32), ("cflag", np.uint32)])
_HDR_BYTES = _HDR_DTYPE.itemsize
assert _HDR_BYTES == 32


def _declare_binned_cache_sig():
    L = lib()
    if getattr(L, "_binned_cache_declared", False):
        return L
    if not hasattr(L, "DmlcTpuBinnedCacheWriterCreate"):
        raise NativeError("libdmlctpu.so predates the binned cache API; "
                          "rebuild the native library")
    P = ctypes.POINTER
    L.DmlcTpuBinnedCacheWriterCreate.argtypes = [
        ctypes.c_char_p, ctypes.c_char_p, P(ctypes.c_void_p)]
    L.DmlcTpuBinnedCacheWriterWriteBlock.argtypes = [
        ctypes.c_void_p, ctypes.c_uint32, ctypes.c_uint64, ctypes.c_uint64,
        ctypes.c_void_p, ctypes.c_uint64]
    L.DmlcTpuBinnedCacheWriterSetCuts.argtypes = [
        ctypes.c_void_p, P(ctypes.c_float), ctypes.c_uint64, ctypes.c_uint64]
    L.DmlcTpuBinnedCacheWriterWriteRaw.argtypes = [
        ctypes.c_void_p, ctypes.c_uint32, ctypes.c_uint32, ctypes.c_uint64,
        ctypes.c_uint64, P(ctypes.c_float), P(ctypes.c_float),
        P(ctypes.c_int32), P(ctypes.c_int32), P(ctypes.c_float),
        P(ctypes.c_int32)]
    L.DmlcTpuBinnedCacheWriterClose.argtypes = [ctypes.c_void_p]
    L.DmlcTpuBinnedCacheWriterFree.argtypes = [ctypes.c_void_p]
    L.DmlcTpuBinnedCacheWriterFree.restype = None
    L.DmlcTpuBinnedCacheReaderCreate.argtypes = [
        ctypes.c_char_p, ctypes.c_int, P(ctypes.c_void_p)]
    L.DmlcTpuBinnedCacheReaderValid.argtypes = [ctypes.c_void_p,
                                                P(ctypes.c_int)]
    L.DmlcTpuBinnedCacheReaderMissing.argtypes = [ctypes.c_void_p,
                                                  P(ctypes.c_int)]
    L.DmlcTpuBinnedCacheReaderError.argtypes = [ctypes.c_void_p,
                                                P(ctypes.c_char_p)]
    L.DmlcTpuBinnedCacheReaderMetaJson.argtypes = [ctypes.c_void_p,
                                                   P(ctypes.c_char_p)]
    L.DmlcTpuBinnedCacheReaderPartMapJson.argtypes = [ctypes.c_void_p,
                                                      P(ctypes.c_char_p)]
    L.DmlcTpuBinnedCacheReaderNextBlock.argtypes = [
        ctypes.c_void_p, P(ctypes.c_void_p), P(ctypes.c_uint64)]
    L.DmlcTpuBinnedCacheReaderNextBlockView.argtypes = [
        ctypes.c_void_p, P(ctypes.c_void_p), P(ctypes.c_uint64),
        P(ctypes.c_int)]
    L.DmlcTpuBinnedCacheReaderBackend.argtypes = [ctypes.c_void_p,
                                                  P(ctypes.c_int)]
    L.DmlcTpuCacheArenaAcquire.argtypes = [ctypes.c_uint64,
                                           P(ctypes.c_void_p)]
    L.DmlcTpuCacheArenaRelease.argtypes = [ctypes.c_void_p]
    L.DmlcTpuBinnedCacheReaderSeekTo.argtypes = [ctypes.c_void_p,
                                                 ctypes.c_uint64]
    L.DmlcTpuBinnedCacheReaderBeforeFirst.argtypes = [ctypes.c_void_p]
    L.DmlcTpuBinnedCacheReaderCorruptSkipped.argtypes = [ctypes.c_void_p]
    L.DmlcTpuBinnedCacheReaderCorruptSkipped.restype = ctypes.c_int64
    L.DmlcTpuBinnedCacheReaderFree.argtypes = [ctypes.c_void_p]
    L.DmlcTpuBinnedCacheReaderFree.restype = None
    L.DmlcTpuBinnedCacheWriterSetCodec.argtypes = [ctypes.c_void_p,
                                                   ctypes.c_int]
    L.DmlcTpuBinnedCacheReaderTakeArena.argtypes = [ctypes.c_void_p,
                                                    P(ctypes.c_void_p)]
    L.DmlcTpuBinnedCacheReaderSetDecode.argtypes = [ctypes.c_void_p,
                                                    ctypes.c_int]
    L.DmlcTpuBlockCodecEnabled.argtypes = []
    L.DmlcTpuBlockCodecFromName.argtypes = [ctypes.c_char_p]
    L.DmlcTpuBlockCodecName.argtypes = [ctypes.c_int]
    L.DmlcTpuBlockCodecName.restype = ctypes.c_char_p
    L.DmlcTpuBlockCodecBound.argtypes = [ctypes.c_uint64]
    L.DmlcTpuBlockCodecBound.restype = ctypes.c_uint64
    L.DmlcTpuBlockCodecEncode.argtypes = [
        ctypes.c_int, ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p,
        ctypes.c_uint64]
    L.DmlcTpuBlockCodecEncode.restype = ctypes.c_int64
    L.DmlcTpuBlockCodecDecode.argtypes = [
        ctypes.c_int, ctypes.c_void_p, ctypes.c_uint64, ctypes.c_void_p,
        ctypes.c_uint64]
    L.DmlcTpuBlockCodecDecode.restype = ctypes.c_int64
    L.DmlcTpuBinnedBlockDecode.argtypes = [
        ctypes.c_void_p, ctypes.c_uint64, P(ctypes.c_void_p),
        P(ctypes.c_uint64)]
    L._binned_cache_declared = True
    return L


# ---- block codec ------------------------------------------------------------

def codec_from_env() -> str:
    """The build-side codec the ``DMLCTPU_BINCACHE_CODEC`` env knob selects
    (default ``raw``); doc/analysis.md knob registry."""
    return os.environ.get("DMLCTPU_BINCACHE_CODEC", "raw") or "raw"


def resolve_codec(codec: Optional[str]) -> str:
    """Normalize a codec request (``None`` defers to the env knob) to a
    canonical name the native side accepts.  Unknown names raise; a
    non-raw codec on a library built with ``-DDMLCTPU_CODEC=0`` warns and
    falls back to ``raw`` (the stub can still READ raw caches but must not
    write compressed blocks it could never decode back)."""
    L = _declare_binned_cache_sig()
    name = (codec_from_env() if codec is None else codec).strip().lower()
    if name == "":
        name = "raw"
    cid = int(L.DmlcTpuBlockCodecFromName(name.encode()))
    if cid < 0:
        raise ValueError(f"unknown bin-cache codec {name!r} "
                         f"(supported: raw, lz4)")
    if cid != 0 and not int(L.DmlcTpuBlockCodecEnabled()):
        LOGGER.warning("bin-cache codec %r requested but libdmlctpu was "
                       "built with DMLCTPU_CODEC=0; writing raw", name)
        return "raw"
    return "raw" if cid == 0 else name


def decode_block_payload(buf: Union[bytes, np.ndarray]):
    """Decode one maybe-compressed block record payload into the raw layout
    :func:`unpack_block` expects.  Raw payloads are returned unchanged (no
    bytes move); compressed payloads decode into a pooled native arena and
    come back as a uint8 view whose finalizer recycles the arena.  The
    dataservice client runs every wire frame through this — workers ship
    stored (possibly compressed) bytes verbatim and never decode."""
    L = _declare_binned_cache_sig()
    a = np.frombuffer(buf, np.uint8) if not isinstance(buf, np.ndarray) \
        else buf
    arena, out_size = ctypes.c_void_p(), ctypes.c_uint64()
    check(L.DmlcTpuBinnedBlockDecode(
        a.ctypes.data_as(ctypes.c_void_p), a.shape[0],
        ctypes.byref(arena), ctypes.byref(out_size)))
    if not arena.value:
        return buf
    n = int(out_size.value)
    cbuf = (ctypes.c_uint8 * n).from_address(arena.value)
    weakref.finalize(cbuf, _arena_release, L, int(arena.value))
    return np.frombuffer(cbuf, np.uint8, n)


# ---- digests & meta ---------------------------------------------------------

def cuts_digest_of(cuts) -> str:
    """Short content digest of a cuts matrix (shape-sensitive)."""
    a = np.ascontiguousarray(np.asarray(cuts, np.float32))
    h = hashlib.sha256(a.tobytes())
    h.update(repr(a.shape).encode())
    return h.hexdigest()[:16]


#: meta fields compared verbatim on open; a mismatch in any is a rebuild
_INVALIDATION_FIELDS = (
    "version", "byte_order", "num_bins", "missing_aware", "sketch_size",
    "sketch_seed", "source_bytes", "num_parts", "virtual_parts", "format",
    "with_qid", "codec",
)


def _compose_meta(uri: str, binner, *, source_bytes: int, num_parts: int,
                  virtual_parts: int, format: str,  # noqa: A002
                  with_qid: bool, cuts: np.ndarray,
                  codec: str = "raw") -> dict:
    pad_bin = int(np.searchsorted(cuts[0], np.float32(0.0), side="right") + 1
                  ) if cuts.size else 1
    return {
        "version": CACHE_META_VERSION,
        "byte_order": sys.byteorder,
        "source": uri,
        "source_bytes": int(source_bytes),
        "num_parts": int(num_parts),
        "virtual_parts": int(virtual_parts),
        "format": str(format),
        "with_qid": bool(with_qid),
        "codec": str(codec),
        "num_bins": int(binner.num_bins),
        "missing_aware": bool(binner.missing_aware),
        "sketch_size": int(binner.sketch_size),
        "sketch_seed": int(binner.sketch_seed),
        "cuts_digest": cuts_digest_of(cuts),
        "cuts_shape": [int(s) for s in cuts.shape],
        "cuts_b64": base64.b64encode(cuts.tobytes()).decode(),
        # bin code a padding lane (index 0, value 0.0) takes under these
        # cuts — kept so every layer pads ebin identically
        "pad_bin": pad_bin,
    }


def _meta_matches(meta: dict, expect: dict,
                  expect_cuts_digest: Optional[str]) -> tuple[bool, str]:
    for k in _INVALIDATION_FIELDS:
        if meta.get(k) != expect.get(k):
            return False, (f"{k} mismatch (cache {meta.get(k)!r} != "
                           f"expected {expect.get(k)!r})")
    if expect_cuts_digest is not None and \
            meta.get("cuts_digest") != expect_cuts_digest:
        return False, (f"cuts_digest mismatch (cache "
                       f"{meta.get('cuts_digest')!r} != binner "
                       f"{expect_cuts_digest!r})")
    return True, ""


def _cuts_from_meta(meta: dict) -> np.ndarray:
    cuts = np.frombuffer(base64.b64decode(meta["cuts_b64"]),
                         np.float32).reshape(meta["cuts_shape"])
    if cuts_digest_of(cuts) != meta["cuts_digest"]:
        raise ValueError("bin cache cuts payload does not match its own "
                         "digest; refusing to adopt")
    return cuts


# ---- native handle wrappers -------------------------------------------------

class _NativeWriter:
    def __init__(self, path: str, meta_json: str):
        self._lib = _declare_binned_cache_sig()
        self._handle = ctypes.c_void_p()
        check(self._lib.DmlcTpuBinnedCacheWriterCreate(
            path.encode(), meta_json.encode(), ctypes.byref(self._handle)))

    def set_codec(self, codec: str) -> None:
        cid = int(self._lib.DmlcTpuBlockCodecFromName(codec.encode()))
        if cid < 0:
            raise ValueError(f"unknown bin-cache codec {codec!r}")
        check(self._lib.DmlcTpuBinnedCacheWriterSetCodec(self._handle, cid))

    def set_cuts(self, cuts: np.ndarray) -> None:
        cuts = np.ascontiguousarray(cuts, np.float32)
        self._keep_cuts = cuts  # pointer must outlive the native copy call
        check(self._lib.DmlcTpuBinnedCacheWriterSetCuts(
            self._handle, cuts.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            cuts.shape[0], cuts.shape[1]))

    def write_raw(self, part_id: int, seq: int, num_rows: int,
                  label: np.ndarray, weight: np.ndarray, row_ptr: np.ndarray,
                  index: np.ndarray, value: np.ndarray,
                  qid: Optional[np.ndarray]) -> None:
        P = ctypes.POINTER
        nnz = int(row_ptr[num_rows])
        f32 = lambda a: np.ascontiguousarray(a, np.float32)  # noqa: E731
        i32 = lambda a: np.ascontiguousarray(a, np.int32)  # noqa: E731
        lab, wgt = f32(label[:num_rows]), f32(weight[:num_rows])
        rp, idx = i32(row_ptr[:num_rows + 1]), i32(index[:nnz])
        val = f32(value[:nnz])
        q = i32(qid[:num_rows]) if qid is not None else None
        check(self._lib.DmlcTpuBinnedCacheWriterWriteRaw(
            self._handle, part_id, seq, num_rows, nnz,
            lab.ctypes.data_as(P(ctypes.c_float)),
            wgt.ctypes.data_as(P(ctypes.c_float)),
            rp.ctypes.data_as(P(ctypes.c_int32)),
            idx.ctypes.data_as(P(ctypes.c_int32)),
            val.ctypes.data_as(P(ctypes.c_float)),
            q.ctypes.data_as(P(ctypes.c_int32)) if q is not None else None))

    def close(self) -> None:
        if self._handle:
            check(self._lib.DmlcTpuBinnedCacheWriterClose(self._handle))
            self.free()

    def free(self) -> None:
        handle, self._handle = self._handle, ctypes.c_void_p()
        if handle:
            try:
                self._lib.DmlcTpuBinnedCacheWriterFree(handle)
            except (AttributeError, TypeError):
                pass

    def __del__(self):
        try:
            self.free()
        except Exception:
            pass


def _reader_handle_free(L, handle_value: int) -> None:
    try:
        L.DmlcTpuBinnedCacheReaderFree(ctypes.c_void_p(handle_value))
    except (AttributeError, TypeError):
        pass  # interpreter teardown: the process is about to unmap anyway


class _ReaderHandleOwner:
    """Owns the native reader handle.  The free (and with it the munmap of
    the zero-copy mapping) runs only once BOTH the :class:`_NativeReader`
    wrapper and every borrowed block view — each pins this object through
    its buffer base chain — are garbage, so ``close()`` can never invalidate
    a view still queued in the repacker or held by an in-flight jax
    transfer (doc/binned_cache.md borrow rules)."""

    def __init__(self, L, handle_value: int):
        self._finalize = weakref.finalize(self, _reader_handle_free, L,
                                          handle_value)


class _NativeReader:
    """Validating cache reader; construction never raises on a bad cache
    (``valid`` turns False and ``error`` says why)."""

    def __init__(self, path: str, recover: bool = False):
        self._lib = _declare_binned_cache_sig()
        self._handle = ctypes.c_void_p()
        self._keep: Optional[_ReaderHandleOwner] = None
        check(self._lib.DmlcTpuBinnedCacheReaderCreate(
            path.encode(), 1 if recover else 0, ctypes.byref(self._handle)))
        self._keep = _ReaderHandleOwner(self._lib, int(self._handle.value))
        flag = ctypes.c_int()
        check(self._lib.DmlcTpuBinnedCacheReaderValid(self._handle,
                                                      ctypes.byref(flag)))
        self.valid = bool(flag.value)
        check(self._lib.DmlcTpuBinnedCacheReaderMissing(self._handle,
                                                        ctypes.byref(flag)))
        self.missing = bool(flag.value)
        s = ctypes.c_char_p()
        check(self._lib.DmlcTpuBinnedCacheReaderError(self._handle,
                                                      ctypes.byref(s)))
        self.error = (s.value or b"").decode()
        self.meta: dict = {}
        self.part_map: dict = {}
        if self.valid:
            check(self._lib.DmlcTpuBinnedCacheReaderMetaJson(
                self._handle, ctypes.byref(s)))
            self.meta = json.loads((s.value or b"{}").decode())
            # pre-codec caches carry no codec key; they ARE raw caches, so
            # normalizing here (not at comparison sites) keeps them serving
            # zero-copy with no rebuild
            self.meta.setdefault("codec", "raw")
            check(self._lib.DmlcTpuBinnedCacheReaderPartMapJson(
                self._handle, ctypes.byref(s)))
            self.part_map = {int(p["id"]): p for p in
                             json.loads((s.value or b"{}").decode()
                                        ).get("parts", [])}

    def next_block(self) -> Optional[bytes]:
        data, size = ctypes.c_void_p(), ctypes.c_uint64()
        rc = check(self._lib.DmlcTpuBinnedCacheReaderNextBlock(
            self._handle, ctypes.byref(data), ctypes.byref(size)))
        if rc != 1:
            return None
        # materializing bytes out of the native block buffer is itself a
        # copy the zero-copy path (next_block_view) avoids
        telemetry.counter_add("cache.bytes_copied", int(size.value))
        return ctypes.string_at(data, size.value)

    def next_block_view(self) -> Optional[np.ndarray]:
        """Next block payload as a uint8 numpy view — the zero-copy hit path.

        A borrowed view points straight into the reader's mapping/arena and
        stays valid until the last view AND this reader are garbage (its
        buffer base chain pins the native handle).  Non-borrowed scratch
        (streaming backend, reassembled magic-split records) is copied out
        here — counted in ``cache.bytes_copied`` — so callers always get a
        stable array either way.

        A compressed record comes back already decoded into a pooled arena;
        taking the arena here (``DmlcTpuBinnedCacheReaderTakeArena``) and
        pinning it by a release finalizer lets the native reader decode the
        NEXT record into a fresh pool buffer while this view is still
        queued in the repacker — the double-buffered decode/repack
        overlap."""
        data, size = ctypes.c_void_p(), ctypes.c_uint64()
        borrowed = ctypes.c_int()
        rc = check(self._lib.DmlcTpuBinnedCacheReaderNextBlockView(
            self._handle, ctypes.byref(data), ctypes.byref(size),
            ctypes.byref(borrowed)))
        if rc != 1:
            return None
        n = int(size.value)
        if n == 0 or not data.value:
            return np.empty(0, np.uint8)
        arena = ctypes.c_void_p()
        check(self._lib.DmlcTpuBinnedCacheReaderTakeArena(
            self._handle, ctypes.byref(arena)))
        cbuf = (ctypes.c_uint8 * n).from_address(data.value)
        if arena.value:
            # decoded block: the view rides the arena we now own, not the
            # reader's mapping; recycle it once every view is garbage
            weakref.finalize(cbuf, _arena_release, self._lib,
                             int(arena.value))
            return np.frombuffer(cbuf, np.uint8, n)
        cbuf._owner = self._keep  # view -> handle keepalive, never the reverse
        a = np.frombuffer(cbuf, np.uint8, n)
        if not borrowed.value:
            a = a.copy()  # scratch is only valid until the next call
            telemetry.counter_add("cache.bytes_copied", n)
        return a

    @property
    def backend(self) -> int:
        """Read backend this open resolved to: 0 streaming fallback,
        1 mmap, 2 O_DIRECT arena (doc/binned_cache.md)."""
        out = ctypes.c_int()
        check(self._lib.DmlcTpuBinnedCacheReaderBackend(self._handle,
                                                        ctypes.byref(out)))
        return int(out.value)

    def set_decode(self, decode: bool) -> None:
        """Toggle inline decode; False serves records exactly as stored
        (compressed payloads included) — the dataservice worker's mode."""
        check(self._lib.DmlcTpuBinnedCacheReaderSetDecode(
            self._handle, 1 if decode else 0))

    def seek_to(self, offset: int) -> None:
        check(self._lib.DmlcTpuBinnedCacheReaderSeekTo(self._handle, offset))

    def before_first(self) -> None:
        check(self._lib.DmlcTpuBinnedCacheReaderBeforeFirst(self._handle))

    @property
    def corrupt_skipped(self) -> int:
        return int(self._lib.DmlcTpuBinnedCacheReaderCorruptSkipped(
            self._handle))

    def close(self) -> None:
        # drop this wrapper's reference; the native free waits (via
        # _ReaderHandleOwner) for any borrowed views still alive
        self._handle = ctypes.c_void_p()
        self._keep = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass


def unpack_block(buf: Union[bytes, np.ndarray]) -> dict:
    """Decode one cache block payload into host arrays (zero-copy views
    over ``buf`` wherever alignment allows).  ``buf`` may be ``bytes`` or a
    uint8 view from :meth:`_NativeReader.next_block_view` — in the latter
    case every column stays a borrowed view into the cache mapping (the
    base chain keeps the mapping alive)."""
    hdr = np.frombuffer(buf, _HDR_DTYPE, count=1)[0]
    nr, nnz = int(hdr["num_rows"]), int(hdr["nnz"])
    with_qid = bool(hdr["flags"] & 1)
    off = _HDR_BYTES
    def take(dtype, count):
        nonlocal off
        a = np.frombuffer(buf, dtype, count, off)
        off += a.nbytes
        return a
    label = take(np.float32, nr)
    weight = take(np.float32, nr)
    row_ptr = take(np.int32, nr + 1)
    qid = take(np.int32, nr) if with_qid else None
    index = take(np.int32, nnz)
    ebin = take(np.uint8, nnz)
    mask_bits = take(np.uint8, (nnz + 7) // 8)
    emask = np.unpackbits(mask_bits, count=nnz,
                          bitorder="little").astype(bool)
    return {
        "part_id": int(hdr["part_id"]), "seq": int(hdr["seq"]),
        "num_rows": nr, "nnz": nnz, "label": label, "weight": weight,
        "row_ptr": row_ptr, "qid": qid, "index": index, "ebin": ebin,
        "emask": emask,
    }


def bin_entries_np(cuts: np.ndarray, index: np.ndarray,
                   value: np.ndarray, chunk: int = 1 << 16) -> np.ndarray:
    """Host-side replica of ``QuantileBinner.transform_entries`` (and of the
    native ``BinEntryCode``): uint8 codes, NaN -> 0, stray indices binned
    against feature 0.  Chunked so the [n, C] cut gather stays bounded."""
    cuts = np.ascontiguousarray(cuts, np.float32)
    F = cuts.shape[0]
    index = np.asarray(index, np.int64)
    value = np.asarray(value, np.float32)
    out = np.empty(index.shape[0], np.uint8)
    for s in range(0, index.shape[0], chunk):
        fi = index[s:s + chunk]
        v = value[s:s + chunk]
        fi = np.where((fi >= 0) & (fi < F), fi, 0)
        code = (cuts[fi] <= v[:, None]).sum(axis=1) + 1
        out[s:s + chunk] = np.where(np.isnan(v), 0, code).astype(np.uint8)
    return out


# ---- the staged pytree ------------------------------------------------------

@dataclass
class BinnedBatch:
    """Static-shape pre-binned CSR batch (a pytree; arrays live on device).

    The binned analogue of :class:`~dmlc_core_tpu.data.staging.PaddedBatch`:
    ``ebin`` carries each entry's uint8 bin code (what
    ``QuantileBinner.transform_entries`` would compute from the value) and
    ``emask`` its presence bit (``value != 0 and not isnan(value)`` — the
    trainer's ``_entry_arrays`` rule), replacing the float ``value`` column
    entirely.  Padding rows have ``weight == 0`` and empty spans; padding
    nonzero lanes have ``emask == False`` and ``ebin == pad_bin`` (the code
    a value-0 lane takes on the text path, so array-level comparisons hold
    lane for lane).  ``cuts_digest`` is a static field naming the cuts the
    codes were computed under; the trainer refuses a binner whose cuts
    disagree instead of silently mixing bin vocabularies.
    """

    label: jax.Array    # f32 [batch]
    weight: jax.Array   # f32 [batch]
    row_ptr: jax.Array  # i32 [batch + 1] CSR row pointer
    index: jax.Array    # i32 [nnz_pad] column ids
    ebin: jax.Array     # u8 [nnz_pad] bin codes
    emask: jax.Array    # bool [nnz_pad] entry-present mask
    num_rows: jax.Array  # i32 [] true (unpadded) row count
    qid: Optional[jax.Array] = None  # i32 [batch] query ids (ranking)
    cuts_digest: str = ""  # static: digest of the cuts behind ebin

    @property
    def batch_size(self) -> int:
        return self.label.shape[0]

    def row_ids(self) -> jax.Array:
        """COO row id per nonzero (fuses under jit); padding lanes map to
        row ``batch_size - 1`` (their emask is False, so masked compute is
        unaffected)."""
        return csr_row_ids(self.row_ptr, self.index.shape[0])


jax.tree_util.register_dataclass(
    BinnedBatch,
    data_fields=["label", "weight", "row_ptr", "index", "ebin", "emask",
                 "num_rows", "qid"],
    meta_fields=["cuts_digest"])


# ---- repacking blocks into static-shape batches -----------------------------

def _arena_release(L, addr: int) -> None:
    try:
        L.DmlcTpuCacheArenaRelease(ctypes.c_void_p(addr))
    except (AttributeError, TypeError):
        pass  # interpreter teardown


def _acquire_arena_views(specs) -> dict:
    """One pooled staging arena carved into named numpy views.

    ``specs`` is ``[(name, count, dtype), ...]``; each lane starts on a
    64-byte boundary inside a single 4 KiB-aligned arena from the native
    :class:`CacheArenaPool`.  The arena returns to the pool
    (``cache.arena_reuse``) once every view — including any pinned by an
    in-flight jax transfer — is garbage, so repeat epochs recycle instead
    of allocating."""
    L = _declare_binned_cache_sig()
    lanes, total = [], 0
    for name, count, dtype in specs:
        total = (total + 63) & ~63
        lanes.append((name, total, int(count), np.dtype(dtype)))
        total += np.dtype(dtype).itemsize * int(count)
    ptr = ctypes.c_void_p()
    check(L.DmlcTpuCacheArenaAcquire(max(total, 1), ctypes.byref(ptr)))
    buf = (ctypes.c_uint8 * total).from_address(ptr.value)
    weakref.finalize(buf, _arena_release, L, int(ptr.value))
    return {name: np.frombuffer(buf, dt, count, off)
            for name, off, count, dt in lanes}


class _Repacker:
    """Re-pack trimmed cache blocks into fixed-shape host batches with the
    StagedBatcher's padding semantics (rows to ``batch_size``, nonzeros to a
    ``nnz_bucket`` multiple — or exactly ``nnz_max`` with row spill), so the
    cached epoch's batch composition matches the text-parse epoch's.

    Zero-copy aware: the per-entry columns (index / ebin / emask — the bulk
    of every block) are QUEUED as borrowed block views and gathered exactly
    once, straight into a pooled staging arena at emit time; that gather is
    the only time hit-path entry bytes move on the host.  Per-row columns
    (label / weight / qid / row lengths) are a few bytes per row and stay
    in small concatenated staging buffers."""

    def __init__(self, batch_size: int, nnz_bucket: int, nnz_max: int,
                 pad_bin: int, with_qid: bool):
        self._B = int(batch_size)
        self._bucket = max(int(nnz_bucket), 1)
        self._nnz_max = int(nnz_max)
        self._pad_bin = int(pad_bin)
        self._with_qid = bool(with_qid)
        z = lambda dt: np.empty(0, dt)  # noqa: E731
        self._lab, self._wgt = z(np.float32), z(np.float32)
        self._qid = z(np.int32)
        self._len = z(np.int64)
        #: queued per-entry column views, consumed FIFO by _gather_entries;
        #: each dict holds {idx, ebin, emask, start-consumed-offset}
        self._segs: deque = deque()

    def feed(self, blk: dict) -> Iterator[dict]:
        self._lab = np.concatenate([self._lab, blk["label"]])
        self._wgt = np.concatenate([self._wgt, blk["weight"]])
        if self._with_qid:
            q = blk["qid"] if blk["qid"] is not None else \
                np.zeros(blk["num_rows"], np.int32)
            self._qid = np.concatenate([self._qid, q])
        self._len = np.concatenate(
            [self._len, np.diff(blk["row_ptr"]).astype(np.int64)])
        if blk["nnz"]:
            self._segs.append({"idx": blk["index"], "ebin": blk["ebin"],
                               "emask": blk["emask"], "start": 0})
        yield from self._pump(final=False)

    def flush(self) -> Iterator[dict]:
        yield from self._pump(final=True)

    def _gather_entries(self, n: int, idx_out: np.ndarray,
                        ebin_out: np.ndarray, emask_out: np.ndarray) -> None:
        """Move the next ``n`` queued entries into the output lanes — the
        single hit-path gather into the emitted arena."""
        got = 0
        while got < n:
            seg = self._segs[0]
            s = seg["start"]
            take = min(n - got, seg["idx"].shape[0] - s)
            idx_out[got:got + take] = seg["idx"][s:s + take]
            ebin_out[got:got + take] = seg["ebin"][s:s + take]
            emask_out[got:got + take] = seg["emask"][s:s + take]
            seg["start"] = s + take
            got += take
            if seg["start"] == seg["idx"].shape[0]:
                self._segs.popleft()

    def _take_rows(self) -> Optional[int]:
        """Rows of the next full batch, or None if not enough buffered."""
        n = self._len.shape[0]
        if self._nnz_max == 0:
            return self._B if n >= self._B else None
        # spill rule: close the batch when the next row would overflow the
        # fixed nnz budget (every emitted batch then pads to exactly
        # nnz_max), or at batch_size rows
        csum = np.cumsum(self._len[:self._B + 1])
        fit = int(np.searchsorted(csum, self._nnz_max, side="right"))
        take = min(fit, self._B)
        if take == 0:
            raise ValueError(f"a single row has more than nnz_max="
                             f"{self._nnz_max} nonzeros; raise nnz_max")
        if take >= self._B:
            return self._B if n >= self._B else None
        # budget-limited close needs the overflowing row actually buffered
        return take if n > take else None

    def _pump(self, final: bool) -> Iterator[dict]:
        while True:
            take = self._take_rows()
            if take is None:
                break
            yield self._emit(take)
        if final and self._len.shape[0]:
            yield self._emit(self._len.shape[0])

    def _emit(self, nr: int) -> dict:
        B = self._B
        lens = self._len[:nr]
        nnz = int(lens.sum())
        if self._nnz_max:
            nnz_pad = self._nnz_max
        else:
            nnz_pad = max(-(-nnz // self._bucket) * self._bucket,
                          self._bucket)
        specs = [("label", B, np.float32), ("weight", B, np.float32),
                 ("row_ptr", B + 1, np.int32)]
        if self._with_qid:
            specs.append(("qid", B, np.int32))
        specs += [("index", nnz_pad, np.int32), ("ebin", nnz_pad, np.uint8),
                  ("emask", nnz_pad, np.bool_)]
        out = _acquire_arena_views(specs)
        # arenas are recycled: every lane byte is written (data then pad)
        _fill(out["label"], self._lab[:nr], 0)
        _fill(out["weight"], self._wgt[:nr], 0)
        rp = out["row_ptr"]
        rp[0] = 0
        rp[1:nr + 1] = np.cumsum(lens)
        rp[nr + 1:] = rp[nr]
        if self._with_qid:
            _fill(out["qid"], self._qid[:nr], 0)
        self._gather_entries(nnz, out["index"], out["ebin"], out["emask"])
        out["index"][nnz:] = 0
        out["ebin"][nnz:] = self._pad_bin
        out["emask"][nnz:] = False
        batch = {
            "num_rows": nr,
            "label": out["label"],
            "weight": out["weight"],
            "qid": out["qid"] if self._with_qid else None,
            "row_ptr": rp,
            "index": out["index"],
            "ebin": out["ebin"],
            "emask": out["emask"],
        }
        self._lab, self._wgt = self._lab[nr:], self._wgt[nr:]
        if self._with_qid:
            self._qid = self._qid[nr:]
        self._len = self._len[nr:]
        return batch


def _fill(out: np.ndarray, a: np.ndarray, fill) -> None:
    out[:a.shape[0]] = a
    out[a.shape[0]:] = fill


# ---- build ------------------------------------------------------------------

def _source_total_bytes(uri: str, format: str) -> int:  # noqa: A002
    L = lib()
    h = ctypes.c_void_p()
    fmt = b"recordio" if format == "recordio" else b"text"
    check(L.DmlcTpuInputSplitCreate(uri.split("#", 1)[0].encode(), b"", 0, 1,
                                    fmt, 0, 0, 0, ctypes.byref(h)))
    try:
        return int(L.DmlcTpuInputSplitTotalSize(h))
    finally:
        L.DmlcTpuInputSplitFree(h)


def _drain_host(it: DeviceStagingIter) -> Iterator[dict]:
    """Host batches of a DeviceStagingIter without device staging — the
    build/sketch passes never touch jax."""
    L = it._lib
    with it._lock:
        check(L.DmlcTpuStagedBatcherBeforeFirst(it._handle))
        while True:
            c = _StagedBatchOwnedC()
            if check(L.DmlcTpuStagedBatcherNextOwned(
                    it._handle, ctypes.byref(c))) != 1:
                return
            yield it._wrap_owned(c)


def build_bin_cache(uri: str, cache_path: str, binner, *,
                    num_parts: int = 1, format: str = "auto",  # noqa: A002
                    batch_size: int = 4096, nnz_bucket: int = 1 << 16,
                    with_qid: bool = False, buffer_mb: int = 64,
                    codec: Optional[str] = None) -> dict:
    """Build the binned cache for ``uri`` at ``cache_path``; returns meta.

    ``codec`` selects the optional block codec (``"raw"`` / ``"lz4"``;
    ``None`` defers to ``DMLCTPU_BINCACHE_CODEC``): non-raw builds write
    bitshuffle+LZ4-compressed block records (doc/binned_cache.md, "Block
    codec") that readers decode back bit-identically.

    An unfitted ``binner`` (``cuts is None``) gets a sketch pass first —
    one full parse feeding ``partial_fit_sparse`` then ``finalize()`` — so
    the build is two text parses; a prefit binner builds in one.  The build
    pass drains each GLOBAL virtual part through its own parser cursor and
    writes trimmed blocks via the native binning writer (per-entry codes
    computed in C++, bit-identical to ``transform_entries``).  The cache is
    written to a temp path and renamed in atomically; a failure leaves no
    (or an invalid, sentinel-headed) cache behind.
    """
    total = _source_total_bytes(uri, format)
    V = _pick_virtual_parts(total, num_parts)
    opts = dict(batch_size=batch_size, nnz_bucket=nnz_bucket, format=format,
                with_qid=with_qid, buffer_mb=buffer_mb, autotune=False)

    if binner.cuts is None:
        it = DeviceStagingIter(uri, part=0, num_parts=1, **opts)
        try:
            for w in _drain_host(it):
                nr = w["num_rows"]
                if nr == 0:
                    continue
                nnz = int(w["row_ptr"][nr])
                idx = np.asarray(w["index"][:nnz], np.int64)
                val = np.asarray(w["value"][:nnz], np.float32)
                binner.partial_fit_sparse(idx, val,
                                          int(idx.max(initial=-1)) + 1)
        finally:
            it.close()
        binner.finalize()

    codec = resolve_codec(codec)
    cuts = np.ascontiguousarray(np.asarray(binner.cuts), np.float32)
    meta = _compose_meta(uri, binner, source_bytes=total, num_parts=num_parts,
                         virtual_parts=V, format=format, with_qid=with_qid,
                         cuts=cuts, codec=codec)
    tmp = f"{cache_path}.tmp.{os.getpid()}"
    writer = _NativeWriter(tmp, json.dumps(meta))
    t0 = time.monotonic()
    try:
        writer.set_codec(codec)
        writer.set_cuts(cuts)
        for g in range(num_parts * V):
            it = DeviceStagingIter(uri, part=g, num_parts=num_parts * V,
                                   **opts)
            seq = 0
            try:
                for w in _drain_host(it):
                    nr = w["num_rows"]
                    if nr == 0:
                        continue
                    writer.write_raw(g, seq, nr, w["label"], w["weight"],
                                     w["row_ptr"], w["index"], w["value"],
                                     w["qid"])
                    seq += 1
            finally:
                it.close()
        writer.close()
    except BaseException:
        writer.free()
        try:
            os.remove(tmp)
        except OSError:
            pass
        raise
    os.replace(tmp, cache_path)
    LOGGER.info("built bin cache %s (%d virtual parts) in %.2fs",
                cache_path, num_parts * V, time.monotonic() - t0)
    return meta


# ---- host-level reading -----------------------------------------------------

class BinnedRowIter:
    """Host-level iterator over a binned cache's blocks (the cache-side
    analogue of DiskRowIter): yields unpacked block dicts in part order.
    ``parts`` restricts to a subset of virtual part ids (default: all)."""

    def __init__(self, cache_path: str, parts=None, recover: bool = False):
        self._path = cache_path
        self._recover = bool(recover)
        r = _NativeReader(cache_path, recover)
        if not r.valid:
            err = r.error
            r.close()
            raise ValueError(f"invalid bin cache {cache_path}: {err}")
        self.meta, self.part_map = r.meta, r.part_map
        r.close()
        self._parts = (sorted(self.part_map) if parts is None
                       else [int(p) for p in parts])

    def __iter__(self) -> Iterator[dict]:
        r = _NativeReader(self._path, self._recover)
        try:
            for p in self._parts:
                ent = self.part_map.get(p)
                if ent is None:
                    continue  # part produced no rows at build time
                r.seek_to(int(ent["offset"]))
                for _ in range(int(ent["records"])):
                    buf = r.next_block_view()
                    if buf is None:
                        break  # recover mode skipped a corrupt tail
                    yield unpack_block(buf)
        finally:
            r.close()


# ---- the staging iterator ---------------------------------------------------

class BinnedStagingIter:
    """Stage pre-binned batches into device memory from the epoch cache.

    The cache-hit fast path of the data pipeline: ``__iter__`` first ensures
    a valid, digest-matching cache exists (building or rebuilding it with a
    counted ``cache.rebuilds`` when not — see doc/binned_cache.md for the
    invalidation rules), then streams the cache's uint8 blocks through a
    repack + device-put pipeline, bypassing text parse and binning.  Yields
    :class:`BinnedBatch`.

    ``binner``: a ``QuantileBinner``.  Fitted: its cuts digest becomes part
    of the cache contract (stale cache rebuilds).  Unfitted: the first build
    runs the streaming sketch, and later opens ADOPT the cache's stored cuts
    into the binner (digest-checked), so a fresh process skips the sketch
    entirely.

    Sharding is single-process only on this path (``sharding=`` accepted
    for device placement); multi-host ranks each read their own part range
    of a shared cache (``part``/``num_parts``), and
    :meth:`host_blocks_coordinated` serves tracker-coordinated shard
    handoff from the cache read path.
    """

    def __init__(self, uri: str, binner, cache: Optional[str] = None,
                 batch_size: int = 4096, nnz_bucket: int = 1 << 16,
                 nnz_max: int = 0, part: int = 0, num_parts: int = 1,
                 format: str = "auto", sharding=None,  # noqa: A002
                 prefetch: int = 2, prefetch_depth: Optional[int] = None,
                 with_qid: bool = False, buffer_mb: int = 64,
                 recover: bool = False, codec: Optional[str] = None):
        self._uri = uri
        self._binner = binner
        self._cache_path = cache or uri.split("#", 1)[0] + ".bincache"
        self._codec = resolve_codec(codec)
        self._batch_size = int(batch_size)
        self._nnz_bucket = int(nnz_bucket)
        self._nnz_max = int(nnz_max)
        self._part = int(part)
        self._num_parts = int(num_parts)
        self._format = format
        self._sharding = sharding
        self._prefetch = max(prefetch_depth if prefetch_depth is not None
                             else prefetch, 1)
        self._with_qid = bool(with_qid)
        self._buffer_mb = int(buffer_mb)
        self._recover = bool(recover)
        self._meta: Optional[dict] = None
        self._part_map: dict = {}
        self._fallback_text = False
        self._lock = threading.Lock()
        self.batches_staged = 0
        self.profile = None

    # -- cache lifecycle ------------------------------------------------------
    def _expected_meta(self) -> dict:
        total = _source_total_bytes(self._uri, self._format)
        V = _pick_virtual_parts(total, self._num_parts)
        return {
            "version": CACHE_META_VERSION,
            "byte_order": sys.byteorder,
            "source_bytes": total,
            "num_parts": self._num_parts,
            "virtual_parts": V,
            "format": str(self._format),
            "with_qid": self._with_qid,
            "codec": self._codec,
            "num_bins": int(self._binner.num_bins),
            "missing_aware": bool(self._binner.missing_aware),
            "sketch_size": int(self._binner.sketch_size),
            "sketch_seed": int(self._binner.sketch_seed),
        }

    def _adopt_or_check(self, meta: dict) -> bool:
        if self._binner.cuts is None:
            self._binner.cuts = jnp.asarray(_cuts_from_meta(meta))
            return True
        return cuts_digest_of(self._binner.cuts) == meta["cuts_digest"]

    def ensure_cache(self) -> None:
        """Open-or-(re)build until a valid, contract-matching cache exists.

        Counts ``cache.rebuilds`` exactly once per invalidation (a missing
        file is a first build, not a rebuild).  A build that fails (e.g.
        the ``cache.write.short`` fault) is retried once; failing again,
        the iterator degrades to the text-parse path for its epochs (the
        batch stream stays bit-identical) and the next ``ensure_cache``
        tries the build again.
        """
        self._fallback_text = False
        expect = self._expected_meta()
        digest = (cuts_digest_of(self._binner.cuts)
                  if self._binner.cuts is not None else None)
        r = _NativeReader(self._cache_path, self._recover)
        reason, first_build = None, False
        if r.valid:
            ok, why = _meta_matches(r.meta, expect, digest)
            if ok and self._adopt_or_check(r.meta):
                self._meta, self._part_map = r.meta, r.part_map
                r.close()
                return
            reason = why or "cuts_digest mismatch vs fitted binner"
        else:
            reason, first_build = r.error, r.missing
        r.close()
        if not first_build:
            telemetry.counter_add("cache.rebuilds", 1)
            LOGGER.warning("bin cache %s invalid (%s); rebuilding",
                           self._cache_path, reason)
        for attempt in (1, 2):
            try:
                self._build()
                break
            except Exception as e:
                telemetry.counter_add("cache.build_failed", 1)
                LOGGER.warning("bin cache build attempt %d failed: %s",
                               attempt, e)
                if attempt == 2:
                    LOGGER.warning(
                        "bin cache unavailable; serving this epoch from the"
                        " text-parse path (bit-identical, uncached)")
                    self._fallback_text = True
                    return
        r = _NativeReader(self._cache_path, self._recover)
        try:
            if not r.valid:
                raise RuntimeError(f"freshly built bin cache invalid: "
                                   f"{r.error}")
            ok, why = _meta_matches(r.meta, expect, digest)
            if not ok or not self._adopt_or_check(r.meta):
                raise RuntimeError(f"freshly built bin cache mismatched: "
                                   f"{why}")
            self._meta, self._part_map = r.meta, r.part_map
        finally:
            r.close()

    def _build(self) -> None:
        build_bin_cache(self._uri, self._cache_path, self._binner,
                        num_parts=self._num_parts, format=self._format,
                        batch_size=self._batch_size,
                        nnz_bucket=self._nnz_bucket, with_qid=self._with_qid,
                        buffer_mb=self._buffer_mb, codec=self._codec)

    @property
    def meta(self) -> Optional[dict]:
        return self._meta

    # -- host-side block production -------------------------------------------
    def _my_parts(self) -> list:
        V = int(self._meta["virtual_parts"])
        return list(range(self._part * V, (self._part + 1) * V))

    def _open_global_part(self, g: int) -> Iterator[dict]:
        """One GLOBAL virtual part's unpacked blocks through a fresh cache
        cursor — the read-path twin of RecordStagingIter._open_global_part,
        so tracker-coordinated shard handoff (steal included) serves from
        the thief's cache."""
        ent = self._part_map.get(int(g))
        if ent is None:
            return
        r = _NativeReader(self._cache_path, self._recover)
        try:
            r.seek_to(int(ent["offset"]))
            for _ in range(int(ent["records"])):
                buf = r.next_block_view()
                if buf is None:
                    break
                yield unpack_block(buf)
        finally:
            r.close()

    def host_blocks_coordinated(self, epoch: int = 0, client=None,
                                steal: bool = True) -> Iterator[dict]:
        """Unpacked cache blocks under tracker-coordinated shard ownership
        (claim / steal via the shard board) — mirror of
        ``RecordStagingIter.host_batches_coordinated`` on the cache read
        path.  Call :meth:`ensure_cache` (or run an epoch) first."""
        from dmlc_core_tpu.tracker import metrics as _tracker_metrics
        if self._meta is None:
            self.ensure_cache()
        if self._fallback_text:
            raise RuntimeError("bin cache unavailable (build failed); "
                               "coordinated cache reads need a cache")
        if client is None:
            client = _tracker_metrics.shard_client_from_env(rank=self._part)
        yield from _tracker_metrics.coordinated_parts(
            int(epoch), self._my_parts(), self._open_global_part, client,
            steal=steal)

    def _produce_host(self, emit) -> None:
        pad_bin = int(self._meta.get("pad_bin", 1))
        rp = _Repacker(self._batch_size, self._nnz_bucket, self._nnz_max,
                       pad_bin, self._with_qid)
        emitted = 0
        r = _NativeReader(self._cache_path, self._recover)
        try:
            def send(batch) -> bool:
                nonlocal emitted
                t2 = time.monotonic()
                ok = emit(batch)
                emitted += 1
                telemetry.counter_add("cache.wait_us",
                                      int((time.monotonic() - t2) * 1e6))
                return ok

            try:
                for g in self._my_parts():
                    ent = self._part_map.get(g)
                    if ent is None:
                        continue
                    t0 = time.monotonic()
                    r.seek_to(int(ent["offset"]))
                    for _ in range(int(ent["records"])):
                        buf = r.next_block_view()
                        if buf is None:
                            break
                        outs = list(rp.feed(unpack_block(buf)))
                        telemetry.counter_add(
                            "cache.busy_us",
                            int((time.monotonic() - t0) * 1e6))
                        for b in outs:
                            if not send(b):
                                return
                        t0 = time.monotonic()
                    telemetry.counter_add("cache.busy_us",
                                          int((time.monotonic() - t0) * 1e6))
                for b in rp.flush():
                    if not send(b):
                        return
            except NativeError as e:
                # strict-mode read corruption (torn framing OR a compressed
                # record that fails decode — the cache.codec.corrupt fault
                # lands here).  Before the first emitted batch: invalidate
                # the cache (one counted rebuild; the next ensure_cache sees
                # a missing file, an uncounted first build) and serve this
                # epoch bit-identically from the text path.  Mid-epoch a
                # silent restart would tear the batch stream — re-raise.
                if emitted:
                    raise
                LOGGER.warning(
                    "bin cache %s unreadable (%s); invalidating and serving"
                    " this epoch from the text-parse path", self._cache_path,
                    e)
                telemetry.counter_add("cache.rebuilds", 1)
                try:
                    os.remove(self._cache_path)
                except OSError:
                    pass
                self._produce_host_text(emit)
        finally:
            r.close()

    def _produce_host_text(self, emit) -> None:
        """Degraded mode (cache build failed): parse text and bin on the
        host, emitting the SAME batch stream the cache would have served."""
        cuts = np.ascontiguousarray(np.asarray(self._binner.cuts),
                                    np.float32)
        it = DeviceStagingIter(
            self._uri, batch_size=self._batch_size,
            nnz_bucket=self._nnz_bucket, nnz_max=self._nnz_max,
            part=self._part, num_parts=self._num_parts, format=self._format,
            with_qid=self._with_qid, buffer_mb=self._buffer_mb,
            autotune=False)
        try:
            for w in _drain_host(it):
                v = np.asarray(w["value"], np.float32)
                out = {
                    "num_rows": w["num_rows"],
                    "label": np.asarray(w["label"]),
                    "weight": np.asarray(w["weight"]),
                    "qid": (np.asarray(w["qid"]) if w["qid"] is not None
                            else None),
                    "row_ptr": np.asarray(w["row_ptr"]),
                    "index": np.asarray(w["index"]),
                    "ebin": bin_entries_np(cuts, w["index"], v),
                    "emask": (v != 0) & ~np.isnan(v),
                }
                if not emit(out):
                    return
        finally:
            it.close()

    # -- staging --------------------------------------------------------------
    def _stage(self, w: dict) -> BinnedBatch:
        with_qid = w["qid"] is not None
        num_rows = np.int32(w["num_rows"])
        leaves = ((w["label"], w["weight"], w["row_ptr"], w["index"],
                   w["ebin"], w["emask"], num_rows)
                  + ((w["qid"],) if with_qid else ()))
        # donated put: the runtime may consume the arena-backed leaves
        # in place instead of copying them (DMLCTPU_BINCACHE_DONATE=0
        # opts out; bit-identity vs the non-donated path is tested)
        donate = os.environ.get("DMLCTPU_BINCACHE_DONATE", "1") != "0"
        if self._sharding is None:
            staged = _device_put_maybe_donated(leaves, donate=donate)
        else:
            sh, repl = self._sharding, _replicated_sharding(
                self._sharding)
            shardings = ((sh, sh, repl, sh, sh, sh, repl)
                         + ((sh,) if with_qid else ()))
            staged = _device_put_maybe_donated(leaves, shardings,
                                               donate=donate)
        batch = BinnedBatch(
            label=staged[0], weight=staged[1], row_ptr=staged[2],
            index=staged[3], ebin=staged[4], emask=staged[5],
            num_rows=staged[6],
            qid=staged[7] if with_qid else None,
            cuts_digest=self._meta.get("cuts_digest", "")
            if self._meta else "")
        self.batches_staged += 1
        return batch

    # -- autotuner surface ----------------------------------------------------
    @property
    def knobs(self) -> dict:
        return {"prefetch_depth": self._prefetch}

    def set_knobs(self, prefetch_depth: Optional[int] = None,
                  **_ignored) -> dict:
        """Retune (next-epoch) pipeline knobs; parse-side knobs
        (num_workers / buffer_mb / chunk_bytes) are accepted and ignored —
        a cache-hit epoch has no parse stage to tune."""
        if prefetch_depth is not None:
            self._prefetch = max(int(prefetch_depth), 1)
        return dict(self.knobs, pool_live=False)

    def __iter__(self) -> Iterator[BinnedBatch]:
        with _observability_scope():
            from dmlc_core_tpu import autotune as _at
            tuner = _at.maybe_attach(self)
            if tuner is None:
                yield from self._iter_epoch()
                return
            with tuner.epoch():
                for batch in self._iter_epoch():
                    yield batch
                    tuner.on_batch()

    def _iter_epoch(self) -> Iterator[BinnedBatch]:
        if self._sharding is not None and jax.process_count() > 1:
            raise NotImplementedError(
                "multi-host global-array staging is not wired for the "
                "binned cache path yet; shard by part/num_parts instead")
        with self._lock:
            self.ensure_cache()
        produce = (self._produce_host_text if self._fallback_text
                   else self._produce_host)
        prof = {"host_wait_s": 0.0, "stage_s": 0.0, "emit_wait_s": 0.0,
                "batches": 0}
        self.profile = prof
        host_iter = _staged_iter(produce, self._prefetch,
                                 depth_gauge="cache.queue_depth")

        def produce_device(emit):
            try:
                it = iter(host_iter)
                while True:
                    t0 = time.monotonic()
                    w = next(it, None)
                    t1 = time.monotonic()
                    prof["host_wait_s"] += t1 - t0
                    if w is None:
                        return
                    batch = self._stage(w)
                    t2 = time.monotonic()
                    prof["stage_s"] += t2 - t1
                    ok = emit(batch)
                    t3 = time.monotonic()
                    prof["emit_wait_s"] += t3 - t2
                    prof["batches"] += 1
                    telemetry.counter_add("h2d.wait_us", int((t1 - t0) * 1e6))
                    telemetry.counter_add("h2d.busy_us", int((t2 - t1) * 1e6))
                    telemetry.counter_add("h2d.emit_wait_us",
                                          int((t3 - t2) * 1e6))
                    telemetry.counter_add("h2d.batches", 1)
                    if not ok:
                        return
            finally:
                host_iter.close()

        yield from _staged_iter(produce_device, 2,
                                depth_gauge="h2d.queue_depth",
                                device_feed=True)
