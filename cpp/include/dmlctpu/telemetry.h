// dmlctpu/telemetry.h — process-wide pipeline telemetry: counters, gauges,
// fixed-bucket histograms, and lightweight trace spans.
//
// Design contract (see doc/observability.md):
//  * Counters/gauges are relaxed std::atomic updates — cheap enough to leave
//    on in production hot loops (one uncontended RMW per event).
//  * Named objects are created once under a mutex and live forever; call
//    sites cache the reference in a function-local static so the steady
//    state is a single atomic op with no map lookup.
//  * Histograms use fixed power-of-two buckets (bucket i counts values in
//    (2^(i-1), 2^i], bucket 0 counts v<=1, last bucket is +inf overflow),
//    so Observe() is a clz + one relaxed RMW.
//  * Trace spans buffer into per-thread vectors guarded by a per-thread
//    mutex (uncontended except while a dump walks them) and only when
//    tracing was started; the dump renders Chrome trace-event JSON
//    ("X" complete events, microsecond timestamps) loadable in
//    chrome://tracing / Perfetto.
//  * Compiling with -DDMLCTPU_TELEMETRY=0 replaces everything with inline
//    no-op stubs: call sites compile unchanged and the instrumentation
//    (including the clock reads) vanishes from the binary.
#ifndef DMLCTPU_TELEMETRY_H_
#define DMLCTPU_TELEMETRY_H_

#ifndef DMLCTPU_TELEMETRY
#define DMLCTPU_TELEMETRY 1
#endif

#include <cstdint>
#include <map>
#include <string>

#if DMLCTPU_TELEMETRY
#include <atomic>
#include <chrono>
#endif

namespace dmlctpu {
namespace telemetry {

/*! \brief true when telemetry was compiled in (mirrors the macro at runtime). */
constexpr bool Enabled() { return DMLCTPU_TELEMETRY != 0; }

#if DMLCTPU_TELEMETRY

/*! \brief steady-clock microseconds (CLOCK_MONOTONIC on Linux, same epoch as
 *  Python's time.monotonic, so Python-side spans line up in one trace). */
inline int64_t NowUs() {
  return std::chrono::duration_cast<std::chrono::microseconds>(
             std::chrono::steady_clock::now().time_since_epoch())
      .count();
}

/*! \brief monotonically increasing event count.  All ops relaxed. */
class Counter {
 public:
  void Add(uint64_t n = 1) { v_.fetch_add(n, std::memory_order_relaxed); }
  uint64_t Value() const { return v_.load(std::memory_order_relaxed); }
  void Reset() { v_.store(0, std::memory_order_relaxed); }

 private:
  std::atomic<uint64_t> v_{0};
};

/*! \brief last-writer-wins instantaneous level (queue depth, buffered bytes). */
class Gauge {
 public:
  void Set(int64_t v) { v_.store(v, std::memory_order_relaxed); }
  void Add(int64_t d) { v_.fetch_add(d, std::memory_order_relaxed); }
  int64_t Value() const { return v_.load(std::memory_order_relaxed); }
  void Reset() { v_.store(0, std::memory_order_relaxed); }

 private:
  std::atomic<int64_t> v_{0};
};

/*! \brief fixed power-of-two-bucket histogram.  Bucket i (i<kBuckets-1) has
 *  upper bound 2^i; the last bucket is the +inf overflow.  Observe is a
 *  clz plus three relaxed RMWs; snapshots may be torn across buckets vs
 *  sum/count (monitoring data, not an invariant). */
class Histogram {
 public:
  static constexpr int kBuckets = 32;

  void Observe(uint64_t v) {
    int idx = 0;
    if (v > 1) {
      idx = 64 - __builtin_clzll(v - 1);  // ceil(log2(v))
      if (idx > kBuckets - 1) idx = kBuckets - 1;
    }
    buckets_[idx].fetch_add(1, std::memory_order_relaxed);
    count_.fetch_add(1, std::memory_order_relaxed);
    sum_.fetch_add(v, std::memory_order_relaxed);
  }
  uint64_t Count() const { return count_.load(std::memory_order_relaxed); }
  uint64_t Sum() const { return sum_.load(std::memory_order_relaxed); }
  uint64_t Bucket(int i) const { return buckets_[i].load(std::memory_order_relaxed); }
  void Reset() {
    for (auto& b : buckets_) b.store(0, std::memory_order_relaxed);
    count_.store(0, std::memory_order_relaxed);
    sum_.store(0, std::memory_order_relaxed);
  }

 private:
  std::atomic<uint64_t> buckets_[kBuckets]{};
  std::atomic<uint64_t> count_{0};
  std::atomic<uint64_t> sum_{0};
};

/*! \brief point-in-time copy of a registry's values — the unit the tracker
 *  aggregates across processes.  Counters and histogram buckets merge by
 *  addition (exact: both are event tallies); gauges merge by addition too,
 *  so a merged level gauge reads as the job-wide total (e.g. fleet buffered
 *  bytes).  Merged histogram quantiles stay CONSERVATIVE: every bucket keeps
 *  its upper bound, so a quantile read off the merged buckets never
 *  understates the true per-event quantile of the union. */
struct Snapshot {
  struct Hist {
    uint64_t count = 0;
    uint64_t sum = 0;
    uint64_t buckets[Histogram::kBuckets] = {};
  };
  std::map<std::string, uint64_t> counters;
  std::map<std::string, int64_t> gauges;
  std::map<std::string, Hist> histograms;

  /*! \brief copy the process registry's current values */
  static Snapshot Capture();
  /*! \brief fold another snapshot into this one (see merge rules above) */
  void Merge(const Snapshot& other);
  /*! \brief same JSON shape as Registry::SnapshotJson() */
  std::string ToJson() const;
};

/*! \brief process-wide named registry.  Lookup takes a mutex; returned
 *  references are stable forever, so cache them in a local static:
 *    static Counter& c = Registry::Get()->counter("parse.rows");
 */
class Registry {
 public:
  static Registry* Get();
  Counter& counter(const std::string& name);
  Gauge& gauge(const std::string& name);
  Histogram& histogram(const std::string& name);
  /*! \brief JSON snapshot: {"enabled":true,"counters":{..},"gauges":{..},
   *  "histograms":{name:{"count","sum","buckets"[kBuckets]}}}. */
  std::string SnapshotJson() const;
  /*! \brief zero every registered object (objects stay registered). */
  void ResetAll();

 private:
  friend struct Snapshot;  // Capture() walks impl_ under its mutex
  Registry() = default;
  struct Impl;
  Impl* impl_ = nullptr;  // owned, never freed (process-lifetime singleton)
};

// ---- trace spans ------------------------------------------------------------

/*! \brief install the process-ambient distributed trace context.  Every span
 *  recorded while trace_id != 0 is stamped with (trace_id, parent_span,
 *  lineage) and the trace dump emits them as Chrome-trace args, so a
 *  tracker-side merge can link this process's spans causally under the
 *  originating client span.  trace_id = 0 clears the context (spans revert
 *  to unstamped).  All loads/stores are relaxed: the context is advisory
 *  labeling, not a synchronization edge. */
void SetTraceContext(uint64_t trace_id, uint64_t parent_span, int64_t lineage);
/*! \brief read the ambient context back (out pointers may be null). */
void GetTraceContext(uint64_t* trace_id, uint64_t* parent_span,
                     int64_t* lineage);

/*! \brief start recording spans (clears previously buffered events). */
void TraceStart();
/*! \brief stop recording (buffered events are kept for TraceDumpJson). */
void TraceStop();
/*! \brief true while recording. */
bool TraceActive();
/*! \brief Chrome trace-event JSON of everything buffered since TraceStart. */
std::string TraceDumpJson();
/*! \brief record one complete span.  `name` must be a string literal (the
 *  pointer is stored); use RecordSpanOwned for dynamic names.  `lineage`
 *  (>= 0) is the id of the batch or chunk the span handled and goes out as
 *  args.lineage whether or not a trace context is set; left at -1 the span
 *  takes the recording thread's ScopedLineage, else the ambient context's. */
void RecordSpan(const char* name, int64_t ts_us, int64_t dur_us,
                int64_t lineage = -1);
/*! \brief record one complete span with an owned (copied) name — the C API /
 *  Python path. */
void RecordSpanOwned(const std::string& name, int64_t ts_us, int64_t dur_us,
                     int64_t lineage = -1);
/*! \brief the lineage that spans recorded on THIS thread carry while the
 *  guard lives, for code that holds the chunk around callees that do not
 *  (the sharded worker around its inner parser's parse.chunk).  Thread-local:
 *  unlike the process-wide context slot it cannot be raced by another
 *  worker.  Guards nest; a plain store and load while tracing is off. */
class ScopedLineage {
 public:
  explicit ScopedLineage(int64_t lineage);
  ~ScopedLineage();
  ScopedLineage(const ScopedLineage&) = delete;
  ScopedLineage& operator=(const ScopedLineage&) = delete;

 private:
  int64_t prev_;
};

/*! \brief RAII span: records [ctor, dtor) when tracing is active.  The check
 *  at construction is one relaxed atomic load, so leaving these in hot
 *  paths while tracing is off costs ~nothing. */
class ScopedSpan {
 public:
  explicit ScopedSpan(const char* name) {
    if (TraceActive()) {
      name_ = name;
      t0_ = NowUs();
    }
  }
  ~ScopedSpan() {
    if (name_ != nullptr) RecordSpan(name_, t0_, NowUs() - t0_, lineage_);
  }
  /*! \brief the batch or chunk this span turned out to handle (known only
   *  once the body has run, e.g. a batch's first row's chunk). */
  void set_lineage(int64_t lineage) { lineage_ = lineage; }
  ScopedSpan(const ScopedSpan&) = delete;
  ScopedSpan& operator=(const ScopedSpan&) = delete;

 private:
  const char* name_ = nullptr;
  int64_t t0_ = 0;
  int64_t lineage_ = -1;
};

/*! \brief accumulate elapsed wall time into a counter (microseconds).
 *  Start()/Stop() pairs may be reused; Stop returns the elapsed us. */
class StallTimer {
 public:
  explicit StallTimer(Counter& c) : c_(&c) {}
  void Start() { t0_ = NowUs(); }
  int64_t Stop() {
    int64_t d = NowUs() - t0_;
    if (d > 0) c_->Add(static_cast<uint64_t>(d));
    return d;
  }

 private:
  Counter* c_;
  int64_t t0_ = 0;
};

/*! \brief RAII wall-time accumulator: adds [ctor, dtor) microseconds to a
 *  counter on every exit path (returns and exceptions alike). */
class ScopedAccum {
 public:
  explicit ScopedAccum(Counter& c) : c_(&c), t0_(NowUs()) {}
  ~ScopedAccum() {
    int64_t d = NowUs() - t0_;
    if (d > 0) c_->Add(static_cast<uint64_t>(d));
  }
  ScopedAccum(const ScopedAccum&) = delete;
  ScopedAccum& operator=(const ScopedAccum&) = delete;

 private:
  Counter* c_;
  int64_t t0_;
};

#else  // DMLCTPU_TELEMETRY == 0 — every call site compiles to nothing.

inline int64_t NowUs() { return 0; }

class Counter {
 public:
  void Add(uint64_t = 1) {}
  uint64_t Value() const { return 0; }
  void Reset() {}
};

class Gauge {
 public:
  void Set(int64_t) {}
  void Add(int64_t) {}
  int64_t Value() const { return 0; }
  void Reset() {}
};

class Histogram {
 public:
  static constexpr int kBuckets = 32;
  void Observe(uint64_t) {}
  uint64_t Count() const { return 0; }
  uint64_t Sum() const { return 0; }
  uint64_t Bucket(int) const { return 0; }
  void Reset() {}
};

struct Snapshot {
  struct Hist {
    uint64_t count = 0;
    uint64_t sum = 0;
    uint64_t buckets[Histogram::kBuckets] = {};
  };
  // same surface as the real Snapshot so callers compile unchanged;
  // Capture() always returns empty maps in the stubbed build
  std::map<std::string, uint64_t> counters;
  std::map<std::string, int64_t> gauges;
  std::map<std::string, Hist> histograms;
  static Snapshot Capture() { return Snapshot(); }
  void Merge(const Snapshot&) {}
  std::string ToJson() const { return "{\"enabled\":false}"; }
};

class Registry {
 public:
  static Registry* Get() {
    static Registry r;
    return &r;
  }
  Counter& counter(const std::string&) {
    static Counter c;
    return c;
  }
  Gauge& gauge(const std::string&) {
    static Gauge g;
    return g;
  }
  Histogram& histogram(const std::string&) {
    static Histogram h;
    return h;
  }
  std::string SnapshotJson() const { return "{\"enabled\":false}"; }
  void ResetAll() {}
};

inline void SetTraceContext(uint64_t, uint64_t, int64_t) {}
inline void GetTraceContext(uint64_t* trace_id, uint64_t* parent_span,
                            int64_t* lineage) {
  if (trace_id != nullptr) *trace_id = 0;
  if (parent_span != nullptr) *parent_span = 0;
  if (lineage != nullptr) *lineage = -1;
}

inline void TraceStart() {}
inline void TraceStop() {}
inline bool TraceActive() { return false; }
inline std::string TraceDumpJson() { return "{\"traceEvents\":[]}"; }
inline void RecordSpan(const char*, int64_t, int64_t, int64_t = -1) {}
inline void RecordSpanOwned(const std::string&, int64_t, int64_t,
                            int64_t = -1) {}

class ScopedLineage {
 public:
  explicit ScopedLineage(int64_t) {}
  ScopedLineage(const ScopedLineage&) = delete;
  ScopedLineage& operator=(const ScopedLineage&) = delete;
};

class ScopedSpan {
 public:
  explicit ScopedSpan(const char*) {}
  void set_lineage(int64_t) {}
  ScopedSpan(const ScopedSpan&) = delete;
  ScopedSpan& operator=(const ScopedSpan&) = delete;
};

class StallTimer {
 public:
  explicit StallTimer(Counter&) {}
  void Start() {}
  int64_t Stop() { return 0; }
};

class ScopedAccum {
 public:
  explicit ScopedAccum(Counter&) {}
  ScopedAccum(const ScopedAccum&) = delete;
  ScopedAccum& operator=(const ScopedAccum&) = delete;
};

#endif  // DMLCTPU_TELEMETRY

// ---- well-known pipeline stage metrics --------------------------------------
// One inline accessor per instrumented site so hot loops pay the registry
// lookup exactly once (magic-static init).  Names are the public contract
// consumed by dmlc_core_tpu.telemetry.stall_attribution(); keep in sync with
// doc/observability.md.
namespace stage {

#define DMLCTPU_STAGE_COUNTER(fn, name)            \
  inline Counter& fn() {                           \
    static Counter& c = Registry::Get()->counter(name); \
    return c;                                      \
  }
#define DMLCTPU_STAGE_GAUGE(fn, name)              \
  inline Gauge& fn() {                             \
    static Gauge& g = Registry::Get()->gauge(name); \
    return g;                                      \
  }
#define DMLCTPU_STAGE_HISTOGRAM(fn, name)          \
  inline Histogram& fn() {                         \
    static Histogram& h = Registry::Get()->histogram(name); \
    return h;                                      \
  }

// InputSplit readers: raw chunk IO.
DMLCTPU_STAGE_COUNTER(SplitChunks, "split.chunks")
DMLCTPU_STAGE_COUNTER(SplitBytes, "split.bytes")
// Text-parse pool: per-chunk totals and per-worker busy time.
DMLCTPU_STAGE_COUNTER(ParseChunks, "parse.chunks")
DMLCTPU_STAGE_COUNTER(ParseBytes, "parse.bytes")
DMLCTPU_STAGE_COUNTER(ParseRows, "parse.rows")
DMLCTPU_STAGE_COUNTER(ParseNnz, "parse.nnz")
DMLCTPU_STAGE_COUNTER(ParseBusyUs, "parse.busy_us")
DMLCTPU_STAGE_COUNTER(ParseInputWaitUs, "parse.input_wait_us")
DMLCTPU_STAGE_HISTOGRAM(ParseChunkUs, "parse.chunk_us")
// ShardedParser worker pool: publish totals, buffer level, both stall sides.
DMLCTPU_STAGE_COUNTER(ShardParts, "shard.parts")
DMLCTPU_STAGE_COUNTER(ShardChunks, "shard.chunks")
DMLCTPU_STAGE_COUNTER(ShardBytes, "shard.bytes")
DMLCTPU_STAGE_COUNTER(ShardPartUs, "shard.part_us")
DMLCTPU_STAGE_COUNTER(ShardProducerWaitUs, "shard.producer_wait_us")
DMLCTPU_STAGE_COUNTER(ShardConsumerWaitUs, "shard.consumer_wait_us")
DMLCTPU_STAGE_GAUGE(ShardBufferedBytes, "shard.buffered_bytes")
// Pool position (flight-recorder state): how many virtual parts have been
// claimed by workers vs drained by the consumer.
DMLCTPU_STAGE_GAUGE(ShardNextPart, "shard.next_part")
DMLCTPU_STAGE_GAUGE(ShardEmitPart, "shard.emit_part")
// Live pool knobs (SetPoolKnobs): current worker target + buffer cap, so
// the autotuner's decisions are visible in /metrics and flight records.
DMLCTPU_STAGE_GAUGE(ShardPoolWorkers, "shard.pool_workers")
DMLCTPU_STAGE_GAUGE(ShardPoolBufferBytes, "shard.pool_buffer_bytes")
// StagedBatcher: arena pack/pad.  busy_us excludes time blocked in the
// upstream parser's Next() (that is input_wait_us), so the pair cleanly
// splits "packing is slow" from "packing is starved".
DMLCTPU_STAGE_COUNTER(PackBatches, "pack.batches")
DMLCTPU_STAGE_COUNTER(PackRows, "pack.rows")
DMLCTPU_STAGE_COUNTER(PackBusyUs, "pack.busy_us")
DMLCTPU_STAGE_COUNTER(PackInputWaitUs, "pack.input_wait_us")
DMLCTPU_STAGE_HISTOGRAM(PackBatchUs, "pack.batch_us")
// Packed-but-unconsumed batches across the process's StagedBatchers
// (flight-recorder occupancy: >0 during a stall means the consumer side
// wedged, 0 means packing starved).
DMLCTPU_STAGE_GAUGE(PackQueued, "pack.queued")
// RecordBatcher: unified byte accounting (every native batcher publishes
// chunk bytes here; RecordStagingIter.bytes_read reads the delta).
DMLCTPU_STAGE_COUNTER(RecordBatches, "record.batches")
DMLCTPU_STAGE_COUNTER(RecordBytes, "record.bytes")
// Robust-IO substrate (dmlctpu/retry.h, doc/robustness.md): retries taken,
// operations abandoned after the policy was exhausted, wall time slept in
// backoff (stall_attribution surfaces it as the "io" pseudo-stage), records
// skipped by RecordIO recover mode, part re-parses in the sharded pool, and
// injections fired by the fault registry (fault.h).
DMLCTPU_STAGE_COUNTER(IoRetry, "io.retry")
DMLCTPU_STAGE_COUNTER(IoGiveup, "io.giveup")
DMLCTPU_STAGE_COUNTER(IoRetryWaitUs, "io.retry_wait_us")
DMLCTPU_STAGE_COUNTER(RecordCorruptSkipped, "record.corrupt_skipped")
DMLCTPU_STAGE_COUNTER(ShardPartRetries, "shard.part_retries")
DMLCTPU_STAGE_COUNTER(FaultInjected, "fault.injected")
// Epoch caches (binned_cache.h writer/reader + DiskRowIter validation):
// bytes written during a build pass, bytes served from cache hits, and
// caches rejected by validation (truncated/torn/stale header) — a rebuild
// storm shows up in /metrics and the job table instead of only TLOG lines.
DMLCTPU_STAGE_COUNTER(CacheBuildBytes, "cache.build_bytes")
DMLCTPU_STAGE_COUNTER(CacheHitBytes, "cache.hit_bytes")
DMLCTPU_STAGE_COUNTER(CacheRebuilds, "cache.rebuilds")
// Zero-copy hit path (doc/binned_cache.md "Zero-copy hit path"): bytes that
// were memcpy'd anywhere between the cache file and the repack input —
// streaming-fallback reads, split-record reassembly, legacy NextBlock
// copies.  The bytes_copied / hit_bytes ratio is the proof the mmap path
// is engaged (~0 when it is; ~1+ when every block goes through a decode
// buffer); stall_attribution surfaces it as the cache stage's copy_ratio.
DMLCTPU_STAGE_COUNTER(CacheBytesCopied, "cache.bytes_copied")
// Block codec (block_codec.h, doc/binned_cache.md "Block codec"): counted
// at decode — compressed bytes in, decompressed bytes out, wall time spent
// decoding.  bytes_out / bytes_in is the observed compression ratio on
// every block that actually moved (local stream reads, mmap'd compressed
// records, dataservice client frames); decode_us lands inside the repack
// stage's busy window, so stall_attribution shows decode as cache work,
// not a new stall.
DMLCTPU_STAGE_COUNTER(CacheCodecBytesIn, "cache.codec.bytes_in")
DMLCTPU_STAGE_COUNTER(CacheCodecBytesOut, "cache.codec.bytes_out")
DMLCTPU_STAGE_COUNTER(CacheCodecDecodeUs, "cache.codec.decode_us")
// Which read backend each reader open chose (mmap/O_DIRECT-arena vs the
// streaming fallback) — a fleet of stream_opens where mmap was expected is
// a misconfiguration, not a perf mystery.
DMLCTPU_STAGE_COUNTER(CacheMmapOpens, "cache.mmap_opens")
DMLCTPU_STAGE_COUNTER(CacheStreamOpens, "cache.stream_opens")
// Recycled aligned staging arenas (CacheArenaPool): acquisitions served
// from the free list vs fresh allocations, and bytes currently pooled.
DMLCTPU_STAGE_COUNTER(CacheArenaAlloc, "cache.arena_alloc")
DMLCTPU_STAGE_COUNTER(CacheArenaReuse, "cache.arena_reuse")
DMLCTPU_STAGE_GAUGE(CacheArenaBytes, "cache.arena_bytes")

// Spans that keep their own total (c_api.h, DmlcTpuTelemetryRecordSpanTotal):
// time the binding's main thread spent inside program spans, outermost
// only — what of a process's life the program's spans account for.
DMLCTPU_STAGE_COUNTER(MainSpanUs, "main.span_us")

#undef DMLCTPU_STAGE_COUNTER
#undef DMLCTPU_STAGE_GAUGE
#undef DMLCTPU_STAGE_HISTOGRAM

}  // namespace stage
}  // namespace telemetry
}  // namespace dmlctpu
#endif  // DMLCTPU_TELEMETRY_H_
