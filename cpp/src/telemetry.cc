// telemetry.cc — registry singleton, JSON snapshot rendering, and the
// per-thread trace-span buffers behind dmlctpu/telemetry.h.
#include <dmlctpu/telemetry.h>

#if DMLCTPU_TELEMETRY

#include <algorithm>
#include <cstdio>
#include <cstdlib>
#include <map>
#include <memory>
#include <mutex>
#include <sstream>
#include <vector>

namespace dmlctpu {
namespace telemetry {
namespace {

// Minimal string escape for JSON keys/names (metric names are plain
// identifiers in practice; this keeps arbitrary C-API names safe anyway).
void AppendEscaped(std::string* out, const std::string& s) {
  for (char c : s) {
    switch (c) {
      case '"': *out += "\\\""; break;
      case '\\': *out += "\\\\"; break;
      case '\n': *out += "\\n"; break;
      case '\t': *out += "\\t"; break;
      case '\r': *out += "\\r"; break;
      default:
        if (static_cast<unsigned char>(c) < 0x20) {
          char buf[8];
          std::snprintf(buf, sizeof(buf), "\\u%04x", c);
          *out += buf;
        } else {
          *out += c;
        }
    }
  }
}

// ---- trace state ------------------------------------------------------------

struct TraceEvent {
  const char* lit_name;    // string literal, or nullptr when owned
  std::string owned_name;  // used by RecordSpanOwned (C API / Python spans)
  uint32_t tid;
  int64_t ts_us;
  int64_t dur_us;
  // ambient distributed-trace context, stamped at record time (0 = none)
  uint64_t trace_id = 0;
  uint64_t parent = 0;
  int64_t lineage = -1;
};

// Process-ambient distributed trace context (SetTraceContext).  Three
// independent relaxed atomics: the context is advisory labeling adopted at
// hop boundaries, not a synchronization edge, and a torn read across the
// triple can only mislabel a span recorded during the (rare) swap.
std::atomic<uint64_t> g_ctx_trace_id{0};
std::atomic<uint64_t> g_ctx_parent{0};
std::atomic<int64_t> g_ctx_lineage{-1};
// ScopedLineage's slot: the lineage of the chunk this thread is working on.
thread_local int64_t t_lineage = -1;

// Per-thread buffer.  The shared_ptr in the global list keeps it alive past
// thread exit so TraceDumpJson can still read events from finished workers.
// `events` is a fixed-capacity ring once full: new spans overwrite the
// OLDEST (start walks forward), so an always-on service keeps the newest
// tail for crash forensics instead of freezing the first N spans forever.
struct ThreadTraceBuf {
  std::mutex mu;
  std::vector<TraceEvent> events;
  size_t start = 0;  // index of the oldest event once the ring is full
  uint32_t tid = 0;
  uint64_t dropped = 0;
};

// Ring capacity per thread: DMLCTPU_TRACE_RING_EVENTS, default 2^18
// (~16MB/thread worst case).  Read once — the cap must not move while
// rings are partially rewrapped.
size_t TraceRingCap() {
  static const size_t cap = [] {
    const char* v = std::getenv("DMLCTPU_TRACE_RING_EVENTS");
    if (v != nullptr && v[0] != '\0') {
      const long long n = std::atoll(v);
      if (n > 0) return static_cast<size_t>(std::min<long long>(n, 1 << 24));
    }
    return static_cast<size_t>(1) << 18;
  }();
  return cap;
}

std::atomic<bool> g_trace_active{false};

struct TraceGlobal {
  std::mutex mu;
  std::vector<std::shared_ptr<ThreadTraceBuf>> bufs;
  uint32_t next_tid = 1;
};

TraceGlobal& Trace() {
  static TraceGlobal* g = new TraceGlobal();  // leaked: outlive thread dtors
  return *g;
}

ThreadTraceBuf& LocalBuf() {
  thread_local std::shared_ptr<ThreadTraceBuf> buf = [] {
    auto b = std::make_shared<ThreadTraceBuf>();
    TraceGlobal& g = Trace();
    std::lock_guard<std::mutex> lk(g.mu);
    b->tid = g.next_tid++;
    g.bufs.push_back(b);
    return b;
  }();
  return *buf;
}

void PushEvent(TraceEvent&& ev) {
  ThreadTraceBuf& b = LocalBuf();
  ev.tid = b.tid;
  // the span's own lineage wins, then the thread's, then the context's
  if (ev.lineage < 0) ev.lineage = t_lineage;
  ev.trace_id = g_ctx_trace_id.load(std::memory_order_relaxed);
  if (ev.trace_id != 0) {
    ev.parent = g_ctx_parent.load(std::memory_order_relaxed);
    if (ev.lineage < 0) {
      ev.lineage = g_ctx_lineage.load(std::memory_order_relaxed);
    }
  }
  std::lock_guard<std::mutex> lk(b.mu);
  const size_t cap = TraceRingCap();
  if (b.events.size() >= cap) {
    // drop-oldest: overwrite the ring head so the newest spans survive
    b.events[b.start] = std::move(ev);
    b.start = (b.start + 1) % b.events.size();
    ++b.dropped;
    static Counter& drops = Registry::Get()->counter("trace.events_dropped");
    drops.Add(1);
    return;
  }
  b.events.push_back(std::move(ev));
}

}  // namespace

// ---- Registry ---------------------------------------------------------------

struct Registry::Impl {
  mutable std::mutex mu;
  // std::map: stable node addresses (references survive later insertions)
  // and deterministic (sorted) snapshot order.
  std::map<std::string, Counter> counters;
  std::map<std::string, Gauge> gauges;
  std::map<std::string, Histogram> histograms;
};

Registry* Registry::Get() {
  static Registry* r = [] {
    auto* reg = new Registry();   // leaked: process-lifetime singleton so
    reg->impl_ = new Impl();      // worker threads may log at exit safely
    return reg;
  }();
  return r;
}

Counter& Registry::counter(const std::string& name) {
  std::lock_guard<std::mutex> lk(impl_->mu);
  return impl_->counters[name];
}

Gauge& Registry::gauge(const std::string& name) {
  std::lock_guard<std::mutex> lk(impl_->mu);
  return impl_->gauges[name];
}

Histogram& Registry::histogram(const std::string& name) {
  std::lock_guard<std::mutex> lk(impl_->mu);
  return impl_->histograms[name];
}

std::string Registry::SnapshotJson() const {
  std::lock_guard<std::mutex> lk(impl_->mu);
  std::string out = "{\"enabled\":true,\"counters\":{";
  bool first = true;
  for (const auto& [name, c] : impl_->counters) {
    if (!first) out += ',';
    first = false;
    out += '"';
    AppendEscaped(&out, name);
    out += "\":" + std::to_string(c.Value());
  }
  out += "},\"gauges\":{";
  first = true;
  for (const auto& [name, g] : impl_->gauges) {
    if (!first) out += ',';
    first = false;
    out += '"';
    AppendEscaped(&out, name);
    out += "\":" + std::to_string(g.Value());
  }
  out += "},\"histograms\":{";
  first = true;
  for (const auto& [name, h] : impl_->histograms) {
    if (!first) out += ',';
    first = false;
    out += '"';
    AppendEscaped(&out, name);
    out += "\":{\"count\":" + std::to_string(h.Count()) +
           ",\"sum\":" + std::to_string(h.Sum()) + ",\"buckets\":[";
    for (int i = 0; i < Histogram::kBuckets; ++i) {
      if (i) out += ',';
      out += std::to_string(h.Bucket(i));
    }
    out += "]}";
  }
  out += "}}";
  return out;
}

void Registry::ResetAll() {
  std::lock_guard<std::mutex> lk(impl_->mu);
  for (auto& [name, c] : impl_->counters) c.Reset();
  for (auto& [name, g] : impl_->gauges) g.Reset();
  for (auto& [name, h] : impl_->histograms) h.Reset();
}

// ---- Snapshot ---------------------------------------------------------------

Snapshot Snapshot::Capture() {
  Registry::Impl* impl = Registry::Get()->impl_;
  std::lock_guard<std::mutex> lk(impl->mu);
  Snapshot s;
  for (const auto& [name, c] : impl->counters) s.counters[name] = c.Value();
  for (const auto& [name, g] : impl->gauges) s.gauges[name] = g.Value();
  for (const auto& [name, h] : impl->histograms) {
    Hist& hd = s.histograms[name];
    hd.count = h.Count();
    hd.sum = h.Sum();
    for (int i = 0; i < Histogram::kBuckets; ++i) hd.buckets[i] = h.Bucket(i);
  }
  return s;
}

void Snapshot::Merge(const Snapshot& other) {
  for (const auto& [name, v] : other.counters) counters[name] += v;
  for (const auto& [name, v] : other.gauges) gauges[name] += v;
  for (const auto& [name, oh] : other.histograms) {
    Hist& h = histograms[name];
    h.count += oh.count;
    h.sum += oh.sum;
    for (int i = 0; i < Histogram::kBuckets; ++i) h.buckets[i] += oh.buckets[i];
  }
}

std::string Snapshot::ToJson() const {
  std::string out = "{\"enabled\":true,\"counters\":{";
  bool first = true;
  for (const auto& [name, v] : counters) {
    if (!first) out += ',';
    first = false;
    out += '"';
    AppendEscaped(&out, name);
    out += "\":" + std::to_string(v);
  }
  out += "},\"gauges\":{";
  first = true;
  for (const auto& [name, v] : gauges) {
    if (!first) out += ',';
    first = false;
    out += '"';
    AppendEscaped(&out, name);
    out += "\":" + std::to_string(v);
  }
  out += "},\"histograms\":{";
  first = true;
  for (const auto& [name, h] : histograms) {
    if (!first) out += ',';
    first = false;
    out += '"';
    AppendEscaped(&out, name);
    out += "\":{\"count\":" + std::to_string(h.count) +
           ",\"sum\":" + std::to_string(h.sum) + ",\"buckets\":[";
    for (int i = 0; i < Histogram::kBuckets; ++i) {
      if (i) out += ',';
      out += std::to_string(h.buckets[i]);
    }
    out += "]}";
  }
  out += "}}";
  return out;
}

// ---- trace API --------------------------------------------------------------

namespace {
// 016x hex rendering for 64-bit trace/span ids: JSON numbers lose precision
// past 2^53 in JS consumers (Perfetto), so ids travel as strings.
std::string HexId(uint64_t v) {
  char buf[20];
  std::snprintf(buf, sizeof(buf), "%016llx",
                static_cast<unsigned long long>(v));
  return std::string(buf);
}
}  // namespace

void SetTraceContext(uint64_t trace_id, uint64_t parent_span,
                     int64_t lineage) {
  g_ctx_trace_id.store(trace_id, std::memory_order_relaxed);
  g_ctx_parent.store(parent_span, std::memory_order_relaxed);
  g_ctx_lineage.store(lineage, std::memory_order_relaxed);
}

void GetTraceContext(uint64_t* trace_id, uint64_t* parent_span,
                     int64_t* lineage) {
  if (trace_id != nullptr) {
    *trace_id = g_ctx_trace_id.load(std::memory_order_relaxed);
  }
  if (parent_span != nullptr) {
    *parent_span = g_ctx_parent.load(std::memory_order_relaxed);
  }
  if (lineage != nullptr) {
    *lineage = g_ctx_lineage.load(std::memory_order_relaxed);
  }
}

bool TraceActive() { return g_trace_active.load(std::memory_order_relaxed); }

void TraceStart() {
  TraceGlobal& g = Trace();
  std::lock_guard<std::mutex> lk(g.mu);
  for (auto& b : g.bufs) {
    std::lock_guard<std::mutex> blk(b->mu);
    b->events.clear();
    b->start = 0;
    b->dropped = 0;
  }
  g_trace_active.store(true, std::memory_order_release);
}

void TraceStop() { g_trace_active.store(false, std::memory_order_release); }

ScopedLineage::ScopedLineage(int64_t lineage) : prev_(t_lineage) {
  t_lineage = lineage;
}

ScopedLineage::~ScopedLineage() { t_lineage = prev_; }

void RecordSpan(const char* name, int64_t ts_us, int64_t dur_us,
                int64_t lineage) {
  PushEvent(TraceEvent{name, std::string(), 0, ts_us, dur_us, 0, 0, lineage});
}

void RecordSpanOwned(const std::string& name, int64_t ts_us, int64_t dur_us,
                     int64_t lineage) {
  PushEvent(TraceEvent{nullptr, name, 0, ts_us, dur_us, 0, 0, lineage});
}

std::string TraceDumpJson() {
  TraceGlobal& g = Trace();
  std::lock_guard<std::mutex> lk(g.mu);
  std::string out = "{\"displayTimeUnit\":\"ms\",\"traceEvents\":[";
  bool first = true;
  uint64_t dropped = 0;
  for (auto& b : g.bufs) {
    std::lock_guard<std::mutex> blk(b->mu);
    dropped += b->dropped;
    // walk the ring oldest-first so the dump stays chronological per thread
    for (size_t i = 0; i < b->events.size(); ++i) {
      const TraceEvent& ev = b->events[(b->start + i) % b->events.size()];
      if (!first) out += ',';
      first = false;
      out += "{\"name\":\"";
      if (ev.lit_name != nullptr) {
        AppendEscaped(&out, ev.lit_name);
      } else {
        AppendEscaped(&out, ev.owned_name);
      }
      out += "\",\"cat\":\"dmlctpu\",\"ph\":\"X\",\"pid\":1,\"tid\":" +
             std::to_string(ev.tid) + ",\"ts\":" + std::to_string(ev.ts_us) +
             ",\"dur\":" + std::to_string(ev.dur_us);
      if (ev.trace_id != 0) {
        out += ",\"args\":{\"trace_id\":\"" + HexId(ev.trace_id) +
               "\",\"parent\":\"" + HexId(ev.parent) +
               "\",\"lineage\":" + std::to_string(ev.lineage) + "}";
      } else if (ev.lineage >= 0) {
        out += ",\"args\":{\"lineage\":" + std::to_string(ev.lineage) + "}";
      }
      out += "}";
    }
  }
  out += "],\"otherData\":{\"dropped_events\":" + std::to_string(dropped) + "}}";
  return out;
}

}  // namespace telemetry
}  // namespace dmlctpu

#endif  // DMLCTPU_TELEMETRY
