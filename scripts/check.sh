#!/usr/bin/env bash
# Local "never ship red" gate: build + all native suites + pytest.
# Installed as .git/hooks/pre-commit by scripts/install_hooks.sh.
# Mirrors the reference's per-push CI contract
# (reference scripts/test_script.sh:19-40) as a local pre-commit check,
# since no CI runner executes .github/workflows/ci.yml in this environment.
#
# Two tiers (measured on this machine, idle):
#   default      incremental ninja (~s when clean) + 8 native suites (~10s)
#                + pytest -m "not slow" (~60-90s)    -> pre-commit
#   --full       everything incl. @pytest.mark.slow (GBDT fits, 2-process
#                multihost, interpret-mode pallas forests; ~10 min)
#                                                    -> round-end / CI
# DMLCTPU_CHECK_FAST=1 skips pytest entirely (native-only, tight C++ loops).
set -euo pipefail
cd "$(dirname "$0")/.."

FULL=0
[[ "${1:-}" == "--full" ]] && FULL=1

# Static contract tier (doc/analysis.md): sub-second, so it runs first —
# a name drift across the ctypes/telemetry/fault/knob seams fails the gate
# before anything compiles.
if ! python scripts/analyze.py >/tmp/dmlctpu_check_analyze.log 2>&1; then
  cat /tmp/dmlctpu_check_analyze.log >&2
  echo "check.sh: CONTRACT ANALYZER FAILED (log: /tmp/dmlctpu_check_analyze.log)" >&2
  exit 1
fi

# Build under the same lock _native.py's on-demand build takes: two
# concurrent `cmake -B` configures of one tree corrupt each other's
# CMakeFiles/ and both fail (seen: this gate racing a Python import).
mkdir -p build
exec 9>build/.dmlctpu_build_lock
flock 9
cmake -S . -B build -G Ninja -DCMAKE_BUILD_TYPE=Release >/dev/null
ninja -C build >/dev/null

# Keep the exclusive lock through the native-suite loop: a concurrent
# rebuilder relinking ./build/test_* while we execute them means ETXTBSY
# or mixed old/new binaries.  MUST release before pytest — _native.py's
# loader takes a shared lock on this file from child processes, which
# would deadlock against our held exclusive one.
for t in test_core test_runtime test_data test_endian test_input_split test_remote_fs test_telemetry test_timeseries; do
  if ! ./build/"$t" >/tmp/dmlctpu_check_$t.log 2>&1; then
    echo "check.sh: NATIVE SUITE FAILED: $t (log: /tmp/dmlctpu_check_$t.log)" >&2
    exit 1
  fi
done

# ThreadSanitizer tier: test_data is the parser/staging suite, so this gives
# the persistent parse pool (text_parser.h) and the sharded staging pool
# (sharded_parser.h) a TSan pass on every check; test_telemetry adds the
# registry/trace-buffer/log-sink concurrency (snapshot during an active
# pipeline, sink swap under concurrent emits).  cmake configures
# DMLCTPU_ENABLE_SANITIZER=ON; containers without cmake/ninja fall back to a
# direct g++ TSan build (mirrors _native.py's _build_direct fallback).
mkdir -p build/tsan
for t in test_data test_telemetry; do
  tsan_bin=build/tsan/$t
  if command -v cmake >/dev/null && command -v ninja >/dev/null; then
    cmake -S . -B build/tsan -G Ninja -DDMLCTPU_ENABLE_SANITIZER=ON \
          -DDMLCTPU_SANITIZER=thread >/dev/null
    ninja -C build/tsan "$t" >/dev/null
  else
    g++ -O1 -g -std=c++20 -fsanitize=thread -fno-omit-frame-pointer -pthread \
        -I cpp/include -I cpp cpp/tests/"$t".cc cpp/src/*.cc \
        cpp/src/io/*.cc cpp/src/data/*.cc -ldl -o "$tsan_bin"
  fi
  if ! "$tsan_bin" >/tmp/dmlctpu_check_tsan_$t.log 2>&1; then
    echo "check.sh: TSAN SUITE FAILED: $t (log: /tmp/dmlctpu_check_tsan_$t.log)" >&2
    exit 1
  fi
  if grep -q "WARNING: ThreadSanitizer" /tmp/dmlctpu_check_tsan_$t.log; then
    echo "check.sh: TSAN RACE REPORTED (log: /tmp/dmlctpu_check_tsan_$t.log)" >&2
    exit 1
  fi
done

# ASan+UBSan tier: same suites as TSan, one combined address+undefined
# build (separate builds would double the compile cost on this 1-core box
# and the two sanitizers compose).  -fno-sanitize-recover=all turns every
# UBSan diagnostic into an abort so a report can never scroll by green;
# the grep below catches ASan reports from forked children whose exit
# status a suite might swallow.  test_data includes the native bincache
# suite — mmap-borrowed block views, the recycled arena pool, and the
# truncated-mapping fallback (doc/binned_cache.md "zero-copy hit path")
# are exactly the lifetime bugs this tier exists to catch.
mkdir -p build/asan
for t in test_data test_telemetry; do
  asan_bin=build/asan/$t
  if command -v cmake >/dev/null && command -v ninja >/dev/null; then
    cmake -S . -B build/asan -G Ninja -DDMLCTPU_ENABLE_SANITIZER=ON \
          -DDMLCTPU_SANITIZER=address,undefined >/dev/null
    ninja -C build/asan "$t" >/dev/null
  else
    g++ -O1 -g -std=c++20 -fsanitize=address,undefined \
        -fno-sanitize-recover=all -fno-omit-frame-pointer -pthread \
        -I cpp/include -I cpp cpp/tests/"$t".cc cpp/src/*.cc \
        cpp/src/io/*.cc cpp/src/data/*.cc -ldl -o "$asan_bin"
  fi
  if ! "$asan_bin" >/tmp/dmlctpu_check_asan_$t.log 2>&1; then
    echo "check.sh: ASAN/UBSAN SUITE FAILED: $t (log: /tmp/dmlctpu_check_asan_$t.log)" >&2
    exit 1
  fi
  if grep -Eq "ERROR: AddressSanitizer|runtime error:" /tmp/dmlctpu_check_asan_$t.log; then
    echo "check.sh: ASAN/UBSAN REPORT (log: /tmp/dmlctpu_check_asan_$t.log)" >&2
    exit 1
  fi
done

# Telemetry-opt-out tier: the instrumentation contract says every call site
# compiles to nothing under -DDMLCTPU_TELEMETRY=0.  Build the parser/staging
# suite and the telemetry suite against the stubbed header and run both —
# test_telemetry's assertions flip to the stubbed expectations, and
# test_data passing proves the pipeline is bit-identical without telemetry.
mkdir -p build/notelemetry
for t in test_data test_telemetry test_timeseries; do
  nt_bin=build/notelemetry/$t
  if command -v cmake >/dev/null && command -v ninja >/dev/null; then
    cmake -S . -B build/notelemetry -G Ninja -DCMAKE_BUILD_TYPE=Release \
          -DDMLCTPU_TELEMETRY=OFF >/dev/null
    ninja -C build/notelemetry "$t" >/dev/null
  else
    g++ -O1 -g -std=c++20 -DDMLCTPU_TELEMETRY=0 -pthread \
        -I cpp/include -I cpp cpp/tests/"$t".cc cpp/src/*.cc \
        cpp/src/io/*.cc cpp/src/data/*.cc -ldl -o "$nt_bin"
  fi
  if ! "$nt_bin" >/tmp/dmlctpu_check_notelemetry_$t.log 2>&1; then
    echo "check.sh: NOTELEMETRY SUITE FAILED: $t (log: /tmp/dmlctpu_check_notelemetry_$t.log)" >&2
    exit 1
  fi
done

# Fault-injection-opt-out tier: fault.h promises the same compile-out
# contract as telemetry (-DDMLCTPU_FAULTS=0 stubs every point).  Build and
# run the recordio/staging suites against the stubbed header: test_core's
# recover-mode tests and test_data's retry tests must degrade to their
# stubbed expectations, and everything else must be bit-identical.
mkdir -p build/nofaults
for t in test_core test_data; do
  nf_bin=build/nofaults/$t
  if command -v cmake >/dev/null && command -v ninja >/dev/null; then
    cmake -S . -B build/nofaults -G Ninja -DCMAKE_BUILD_TYPE=Release \
          -DDMLCTPU_FAULTS=OFF >/dev/null
    ninja -C build/nofaults "$t" >/dev/null
  else
    # -rdynamic: test_core's stack-trace test needs symbol names from
    # backtrace_symbols (the cmake build links test binaries the same way)
    g++ -O1 -g -std=c++20 -DDMLCTPU_FAULTS=0 -pthread -rdynamic \
        -I cpp/include -I cpp cpp/tests/"$t".cc cpp/src/*.cc \
        cpp/src/io/*.cc cpp/src/data/*.cc -ldl -o "$nf_bin"
  fi
  if ! "$nf_bin" >/tmp/dmlctpu_check_nofaults_$t.log 2>&1; then
    echo "check.sh: NOFAULTS SUITE FAILED: $t (log: /tmp/dmlctpu_check_nofaults_$t.log)" >&2
    exit 1
  fi
done

# Codec-opt-out tier: block_codec.h promises the same compile-out contract
# (-DDMLCTPU_CODEC=0 stubs bitshuffle+LZ4 out).  Build and run the data
# suite against the stubbed header: writers must store every record raw,
# the lz4 knob spelling must be refused, compressed caches must read as
# corrupt — and the raw paths must stay bit-identical.  (The ASan/UBSan
# tier above covers the codec-ON decode paths, bounds checks included.)
mkdir -p build/nocodec
for t in test_data; do
  nc_bin=build/nocodec/$t
  if command -v cmake >/dev/null && command -v ninja >/dev/null; then
    cmake -S . -B build/nocodec -G Ninja -DCMAKE_BUILD_TYPE=Release \
          -DDMLCTPU_CODEC=OFF >/dev/null
    ninja -C build/nocodec "$t" >/dev/null
  else
    g++ -O1 -g -std=c++20 -DDMLCTPU_CODEC=0 -pthread -rdynamic \
        -I cpp/include -I cpp cpp/tests/"$t".cc cpp/src/*.cc \
        cpp/src/io/*.cc cpp/src/data/*.cc -ldl -o "$nc_bin"
  fi
  if ! "$nc_bin" >/tmp/dmlctpu_check_nocodec_$t.log 2>&1; then
    echo "check.sh: NOCODEC SUITE FAILED: $t (log: /tmp/dmlctpu_check_nocodec_$t.log)" >&2
    exit 1
  fi
done
flock -u 9

if [[ "${DMLCTPU_CHECK_FAST:-0}" != "1" ]]; then
  if [[ "$FULL" == "1" ]]; then
    python -m pytest tests/ -x -q
  else
    python -m pytest tests/ -x -q -m "not slow"
  fi

  # Watchdog tier: the whole staging suite under an AGGRESSIVE 2 s stall
  # deadline with abort policy.  Every epoch arms the env watchdog via
  # _observability_scope; any spurious stall verdict calls abort() in the
  # test process and the tier goes red — proving the detector stays quiet
  # on busy pipelines (slow epochs, tiny buffers, worker pools) and only
  # ever fires on real wedges.  Tests that inject a REAL stall are safe:
  # their own outer watchdog() context arms first (warn policy), and the
  # env arming nests refcounted inside it without replacing the policy.
  DMLCTPU_WATCHDOG_DEADLINE_S=2 DMLCTPU_WATCHDOG_POLICY=abort \
    python -m pytest tests/test_staging.py -x -q -m "not slow"

  # Faults tier: the whole staging suite with a worker-chunk fault armed
  # from the environment (seeded — every run injects the same failures).
  # The sharded pool's part-retry must absorb every injection: any output
  # drift or surfaced error fails the suite, proving the degradation path
  # is transparent.  Only shard.worker.chunk is armed here — it is retried
  # above the parse, so a green run means bit-identical staging; arming
  # corruption points (recordio.magic) would legitimately fail non-recover
  # readers.
  DMLCTPU_FAULTS="shard.worker.chunk=err@0.02;seed=3" \
    python -m pytest tests/test_staging.py -x -q -m "not slow"

  # Autotune tier: the whole staging suite with the stall-attribution
  # controller armed and deciding every 4 batches.  Every epoch then runs
  # live SetPoolKnobs retunes (worker growth/retire, buffer and chunk
  # moves) against the sharded pool mid-stream; any pool deadlock hangs
  # the suite and any stream perturbation fails the staging assertions —
  # proving armed tuning is transparent to what the model sees.
  DMLCTPU_AUTOTUNE=1 DMLCTPU_AUTOTUNE_WINDOW=4 \
    python -m pytest tests/test_staging.py -x -q -m "not slow"

  # Bincache tier: the binned epoch cache suite WITHOUT the slow-marker
  # filter, so the two-process stolen-shard test runs here too — it proves
  # a tracker-stolen shard is served from the thief's cache read path, and
  # the invalidation matrix proves every header-contract mutation costs
  # exactly one counted rebuild with a bit-identical stream after.
  python -m pytest tests/test_binned_cache.py -x -q

  # Dataservice tier: the staging-service suite WITHOUT the slow-marker
  # filter, so the multi-process proofs run here too — a worker
  # subprocess streaming a bit-identical epoch (and identical GBDT
  # forest) to a client subprocess, a mid-epoch worker SIGKILL with a
  # survivor completing the epoch exactly-once, and one worker serving
  # two client processes off a single parse (doc/dataservice.md).
  python -m pytest tests/test_dataservice.py -x -q

  # Serving tier: the online-scoring suite WITHOUT the slow-marker
  # filter, so the two-process hot-swap proof runs here too — a scoring
  # server subprocess hammered by client threads while a new snapshot
  # lands over the wire, every response bit-identical to the snapshot it
  # names, plus the steady-state zero-retrace census, the 503-never-hang
  # contracts, and both serving fault points armed (doc/serving.md).
  python -m pytest tests/test_serving.py -x -q

  # Sparse-pallas tier: the sparse COO histogram kernel and its GBDT
  # wiring, slow marks included — the interpret-mode kernel parity suite,
  # the feature-sort determinism + sharded-layout psum cases, and the
  # forest-identity fits (batch, streamed, shard_map mesh) that prove the
  # histogram= backends stay drop-in interchangeable.
  python -m pytest tests/test_pallas.py -x -q \
    -k "sparse or empty_shard" -m ""
  python -m pytest tests/test_gbdt.py -x -q \
    -k "sparse_fit_batch_pallas or streamed_pallas or sharded_fit_batch_pallas or histogram_env_knob" -m ""

  # Jobtrace tier: a two-process dataservice epoch with tracing armed —
  # worker subprocess and in-process client both record traces and push
  # them (with NTP-style clock probes) over the 0xff98 heartbeat, then
  # the merged /jobtrace body is validated through the NATIVE JSONReader
  # and the worker's serve spans must carry the client's trace id
  # (doc/observability.md "Distributed tracing").
  python scripts/jobtrace_check.py

  # Timeseries tier: always-on observability end-to-end.  First the whole
  # staging suite with the background sampler armed (fast 200 ms ticks) —
  # every epoch then runs under live ring sampling and resource
  # accounting, and any perturbation of what the model sees fails the
  # staging assertions.  Then the two-process proof: a sampler-armed
  # worker pushes its time-series tail over the 0xff98 channel for the
  # tracker's clock-aligned /jobtimeseries merge, and a SIGABRT'd worker
  # must leave a flight file carrying the trace-ring, time-series, and
  # log tails, validated through the NATIVE JSONReader
  # (doc/observability.md "Always-on operation").
  DMLCTPU_TIMESERIES=1 DMLCTPU_TS_TICK_MS=200 \
    python -m pytest tests/test_staging.py -x -q -m "not slow"
  python scripts/timeseries_check.py

  # Mesh tier: the MeshPlan suite under the forced 8-device host platform
  # (conftest.py pins it for every pytest run, made explicit here because
  # this tier is meaningless without it) — hierarchical-vs-flat allreduce
  # parity on the 1-D and 2-D virtual meshes, the topology/knob surface,
  # the (mesh, axis) tuple adapter, and the chunked-overlap forest
  # bit-identity contract (doc/mesh.md).
  XLA_FLAGS="${XLA_FLAGS:-} --xla_force_host_platform_device_count=8" \
    JAX_PLATFORMS=cpu python -m pytest tests/test_meshplan.py -x -q
fi

tier=$([[ "$FULL" == "1" ]] && echo "full" || echo "fast")
py=$([[ "${DMLCTPU_CHECK_FAST:-0}" == "1" ]] && echo "pytest skipped" || echo "pytest $tier tier + watchdog tier + faults tier + autotune tier + bincache tier + dataservice tier + serving tier + jobtrace tier + timeseries tier + sparse-pallas tier + mesh tier")
echo "check.sh: green (contract analyzer + 8 native suites + TSan parser/staging/telemetry + ASan/UBSan parser/staging/telemetry + notelemetry tier + nofaults tier + nocodec tier + $py)"
