"""chip_smoke.py off the chip: the rehearsal walks every single-device phase
at tiny sizes on the CPU backend (interpreted kernels), and without the
flag a non-TPU backend is a failure that names what JAX found.  What the
script proves about a device it can only prove on one."""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

REPO = Path(__file__).resolve().parent.parent
SMOKE = str(REPO / "chip_smoke.py")


def _run(args, tmp_path, **env_overrides):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_overrides)
    # the smoke checks this checkout's own library, on one CPU device
    # (the mesh phase has its own, slow-tier, test below)
    for name in ("DMLCTPU_LIBRARY_PATH", "XLA_FLAGS",
                 "JAX_COMPILATION_CACHE_DIR"):
        if name not in env_overrides:
            env.pop(name, None)
    return subprocess.run(
        [sys.executable, SMOKE, "--out", str(tmp_path / "out"), *args],
        capture_output=True, text=True, timeout=600, env=env, cwd=str(REPO))


def test_rehearsal_passes_and_says_what_it_is(tmp_path):
    proc = _run(["--rehearse-cpu"], tmp_path)
    assert proc.returncode == 0, proc.stderr[-3000:]
    summary, verdict = map(json.loads, proc.stdout.splitlines())
    # the last line is the verdict alone, with exactly these keys
    assert verdict == {"ok": True, "device": {"platform": "cpu",
                                              "kind": "cpu", "count": 1}}
    assert summary["ok"] is True and summary["rehearsal"] is True
    assert summary["device"] == verdict["device"]
    assert all(p["ok"] for p in summary["phases"].values())
    assert summary["phases"]["mesh"]["skipped"]
    # every kernel ran, and said it was interpreted (off the chip it must be)
    assert len(summary["kernels"]) == 14
    assert all(k["interpret"] for k in summary["kernels"])
    # the last four: the entry lookup against XLA's gather and the entries'
    # push against its scatter-add, bit for bit
    lookups, pushes = summary["kernels"][7:9], summary["kernels"][9:11]
    assert [k["kernel"] for k in lookups] == ["entry_lookup(slots)",
                                              "entry_lookup(grad_hess)"]
    assert all(k["exact"] and k["chunk_visits"] >= k["entries"] // 1024
               for k in lookups)
    assert [k["kernel"] for k in pushes] == ["entry_push(all_live)",
                                             "entry_push(one_station)"]
    assert all(k["exact"] and k["chunk_visits"] >= k["sub_tiles"] > 0
               for k in pushes)
    # one station's runs are fewer sub-tiles than the layout's, all live
    assert pushes[1]["sub_tiles"] < pushes[0]["sub_tiles"] == (
        pushes[0]["entries"] // 1024)
    # the last: the layout's binning against the bisection, bit for bit
    binning = summary["kernels"][11]
    assert binning["kernel"] == "layout_bin" and binning["exact"]
    assert binning["checked"] > binning["runs"]
    # then the dense kernel where its last group of key tiles is short
    short = summary["kernels"][12:]
    assert [(k["kernel"], k["n_nodes"], k["dead_key_tiles"]) for k in short
            ] == [("histogram_gh(short_group)", 8, 5),
                  ("histogram_gh(short_group)", 64, 5)]
    assert all(k["rel_err"] <= 7e-7 for k in short)
    # data and outputs stay under --out, and the large inputs are removed
    assert sorted(p.name for p in (tmp_path / "out").iterdir()) == [
        "forest.ckpt", "summary.json"]


def test_without_the_flag_a_cpu_backend_fails(tmp_path):
    proc = _run([], tmp_path)
    assert proc.returncode != 0
    assert proc.stdout == ""
    assert "jax.default_backend() is 'cpu'" in proc.stderr


def test_expect_devices_is_enforced(tmp_path):
    proc = _run(["--rehearse-cpu", "--expect-devices", "4"], tmp_path)
    assert proc.returncode != 0 and proc.stdout == ""
    assert "--expect-devices 4: JAX found 1 cpu device(s)" in proc.stderr


@pytest.mark.slow  # ~70 s: the mesh phase on eight virtual CPU devices
def test_rehearsal_mesh_phase(tmp_path):
    proc = _run(["--rehearse-cpu", "--expect-devices", "8"], tmp_path,
                XLA_FLAGS="--xla_force_host_platform_device_count=8")
    assert proc.returncode == 0, proc.stderr[-3000:]
    summary, verdict = map(json.loads, proc.stdout.splitlines())
    assert verdict == {"ok": True, "device": {"platform": "cpu",
                                              "kind": "cpu", "count": 8}}
    mesh = summary["phases"]["mesh"]
    assert mesh["ok"] and mesh["plan"]["devices"] == 8
    assert mesh["dryrun_multichip"] == 8


def test_compile_cache_is_placed_from_outside(monkeypatch):
    import jax

    from dmlc_core_tpu import compile_cache
    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes",
            "jax_compilation_cache_include_metadata_in_key",
            "jax_traceback_in_locations_limit")
    saved = {k: getattr(jax.config, k) for k in keys}
    try:
        # set from outside: the directory is JAX's to read, none set in code
        jax.config.update("jax_compilation_cache_dir", "/placed/from/outside")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
        assert compile_cache.configure() == "/placed/from/outside"
        assert jax.config.jax_compilation_cache_dir == "/placed/from/outside"
        # unset: a fixed path inside the checkout
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        assert compile_cache.configure() == str(REPO / ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
