// Telemetry tests: counter/gauge/histogram correctness, registry snapshots
// (including under a live multi-threaded parse), Chrome trace-event JSON
// well-formedness, the bit-identity guard (instrumentation must not change
// parse output), and the C-API/log-sink surface.  The whole suite also runs
// in the DMLCTPU_TELEMETRY=0 tier of scripts/check.sh, where every
// Enabled()-gated assertion flips to the stubbed-out expectations.
#include <atomic>
#include <chrono>
#include <cmath>
#include <cstdlib>
#include <cstring>
#include <fstream>
#include <limits>
#include <map>
#include <set>
#include <sstream>
#include <string>
#include <thread>
#include <vector>

#include "dmlctpu/c_api.h"
#include "dmlctpu/data.h"
#include "dmlctpu/row_block.h"
#include "dmlctpu/json.h"
#include "dmlctpu/logging.h"
#include "dmlctpu/stream.h"
#include "dmlctpu/telemetry.h"
#include "dmlctpu/temp_dir.h"
#include "dmlctpu/watchdog.h"
#include "testing.h"

using namespace dmlctpu;  // NOLINT

namespace {

void WriteFile(const std::string& path, const std::string& content) {
  auto fo = Stream::Create(path.c_str(), "w");
  fo->Write(content.data(), content.size());
}

std::string MakeLibsvm(const std::string& dir, int rows) {
  std::string f = dir + "/telemetry.libsvm";
  std::ostringstream os;
  for (int i = 0; i < rows; ++i) {
    os << (i % 2) << " 1:" << i << ".5 7:2.0 11:" << (i % 13) << "\n";
  }
  WriteFile(f, os.str());
  return f;
}

/*! \brief walk an arbitrary JSON document; throws (via TCHECK) when
 *  malformed.  Returns the number of values visited. */
size_t WalkJson(const std::string& text) {
  std::istringstream is(text);
  JSONReader reader(&is);
  // SkipValue() recurses over any value type, so one call covers the doc
  reader.SkipValue();
  return 1;
}

/*! \brief parse the snapshot JSON into (counters, gauges) maps. */
void ParseSnapshot(const std::string& text, bool* enabled,
                   std::map<std::string, int64_t>* counters,
                   std::map<std::string, int64_t>* gauges) {
  std::istringstream is(text);
  JSONReader reader(&is);
  reader.BeginObject();
  std::string key;
  while (reader.NextObjectItem(&key)) {
    if (key == "enabled") {
      reader.ReadNumber(enabled);
    } else if (key == "counters" || key == "gauges") {
      auto* out = key == "counters" ? counters : gauges;
      reader.BeginObject();
      std::string name;
      while (reader.NextObjectItem(&name)) {
        int64_t v = 0;
        reader.ReadNumber(&v);
        (*out)[name] = v;
      }
    } else {
      reader.SkipValue();
    }
  }
}

struct TraceEventLite {
  std::string name, ph;
  int64_t ts = -1, dur = -1, tid = -1;
};

/*! \brief parse Chrome trace JSON, asserting the envelope shape. */
std::vector<TraceEventLite> ParseTrace(const std::string& text) {
  std::vector<TraceEventLite> events;
  std::istringstream is(text);
  JSONReader reader(&is);
  reader.BeginObject();
  std::string key;
  bool saw_events = false;
  while (reader.NextObjectItem(&key)) {
    if (key != "traceEvents") {
      reader.SkipValue();
      continue;
    }
    saw_events = true;
    reader.BeginArray();
    while (reader.NextArrayItem()) {
      reader.BeginObject();
      TraceEventLite ev;
      std::string k;
      while (reader.NextObjectItem(&k)) {
        if (k == "name") {
          reader.ReadString(&ev.name);
        } else if (k == "ph") {
          reader.ReadString(&ev.ph);
        } else if (k == "ts") {
          reader.ReadNumber(&ev.ts);
        } else if (k == "dur") {
          reader.ReadNumber(&ev.dur);
        } else if (k == "tid") {
          reader.ReadNumber(&ev.tid);
        } else {
          reader.SkipValue();
        }
      }
      events.push_back(ev);
    }
  }
  EXPECT_TRUE(saw_events);
  return events;
}

}  // namespace

TESTCASE(counter_gauge_basics) {
  auto* reg = telemetry::Registry::Get();
  telemetry::Counter& c = reg->counter("test.counter_basics");
  telemetry::Counter& c2 = reg->counter("test.counter_basics");
  EXPECT_TRUE(&c == &c2);  // stable object identity per name
  c.Reset();
  c.Add();
  c.Add(41);
  telemetry::Gauge& g = reg->gauge("test.gauge_basics");
  g.Set(7);
  g.Add(-3);
  if (telemetry::Enabled()) {
    EXPECT_EQV(c.Value(), 42u);
    EXPECT_EQV(g.Value(), int64_t{4});
    c.Reset();
    EXPECT_EQV(c.Value(), 0u);
  } else {
    EXPECT_EQV(c.Value(), 0u);
    EXPECT_EQV(g.Value(), int64_t{0});
  }
}

TESTCASE(counter_concurrent_adds) {
  telemetry::Counter& c =
      telemetry::Registry::Get()->counter("test.counter_mt");
  c.Reset();
  constexpr int kThreads = 4, kAdds = 10000;
  std::vector<std::thread> ts;
  for (int t = 0; t < kThreads; ++t) {
    ts.emplace_back([&c] {
      for (int i = 0; i < kAdds; ++i) c.Add(1);
    });
  }
  for (auto& t : ts) t.join();
  EXPECT_EQV(c.Value(),
             telemetry::Enabled() ? uint64_t{kThreads * kAdds} : 0u);
}

TESTCASE(histogram_power_of_two_buckets) {
  telemetry::Histogram& h =
      telemetry::Registry::Get()->histogram("test.histogram");
  h.Reset();
  // bucket i (i < last) has upper bound 2^i: 0,1 -> bucket 0; 2 -> 1;
  // 3,4 -> 2; 5..8 -> 3; huge values land in the +inf overflow bucket
  h.Observe(0);
  h.Observe(1);
  h.Observe(2);
  h.Observe(3);
  h.Observe(4);
  h.Observe(5);
  h.Observe(~uint64_t{0});
  if (!telemetry::Enabled()) {
    EXPECT_EQV(h.Count(), 0u);
    return;
  }
  EXPECT_EQV(h.Count(), 7u);
  EXPECT_EQV(h.Sum(), 15u + ~uint64_t{0});
  EXPECT_EQV(h.Bucket(0), 2u);
  EXPECT_EQV(h.Bucket(1), 1u);
  EXPECT_EQV(h.Bucket(2), 2u);
  EXPECT_EQV(h.Bucket(3), 1u);
  EXPECT_EQV(h.Bucket(telemetry::Histogram::kBuckets - 1), 1u);
  uint64_t total = 0;
  for (int i = 0; i < telemetry::Histogram::kBuckets; ++i) total += h.Bucket(i);
  EXPECT_EQV(total, h.Count());
}

TESTCASE(snapshot_json_wellformed) {
  auto* reg = telemetry::Registry::Get();
  reg->counter("test.snapshot\"quoted\\name").Add(3);
  reg->gauge("test.snapshot_gauge").Set(-5);
  reg->histogram("test.snapshot_hist").Observe(100);
  std::string js = reg->SnapshotJson();
  WalkJson(js);  // throws on malformed JSON (escaping included)
  bool enabled = false;
  std::map<std::string, int64_t> counters, gauges;
  ParseSnapshot(js, &enabled, &counters, &gauges);
  EXPECT_EQV(enabled, telemetry::Enabled());
  if (telemetry::Enabled()) {
    EXPECT_TRUE(counters.count("test.snapshot\"quoted\\name") == 1);
    EXPECT_TRUE(counters.at("test.snapshot\"quoted\\name") >= 3);
    EXPECT_EQV(gauges.at("test.snapshot_gauge"), int64_t{-5});
  }
}

TESTCASE(trace_json_wellformed_multithreaded) {
  telemetry::TraceStart();
  {
    telemetry::ScopedSpan outer("test.outer");
    std::vector<std::thread> ts;
    for (int t = 0; t < 3; ++t) {
      ts.emplace_back([] {
        for (int i = 0; i < 50; ++i) {
          telemetry::ScopedSpan s("test.worker_span");
        }
      });
    }
    for (auto& t : ts) t.join();
    telemetry::RecordSpanOwned("test.owned \"name\"", telemetry::NowUs(), 5);
  }
  telemetry::TraceStop();
  std::string js = telemetry::TraceDumpJson();
  WalkJson(js);
  auto events = ParseTrace(js);
  if (!telemetry::Enabled()) {
    EXPECT_EQV(events.size(), 0u);
    return;
  }
  size_t workers = 0, owned = 0, outers = 0;
  std::set<int64_t> worker_tids;
  for (const auto& ev : events) {
    EXPECT_EQV(ev.ph, std::string("X"));
    EXPECT_TRUE(ev.ts >= 0 && ev.dur >= 0 && ev.tid >= 1);
    if (ev.name == "test.worker_span") {
      ++workers;
      worker_tids.insert(ev.tid);
    }
    if (ev.name == "test.owned \"name\"") ++owned;
    if (ev.name == "test.outer") ++outers;
  }
  EXPECT_EQV(workers, 150u);
  EXPECT_TRUE(worker_tids.size() == 3);  // one trace lane per thread
  EXPECT_EQV(owned, 1u);
  EXPECT_EQV(outers, 1u);
  // a fresh TraceStart clears the buffered spans
  telemetry::TraceStart();
  telemetry::TraceStop();
  EXPECT_EQV(ParseTrace(telemetry::TraceDumpJson()).size(), 0u);
}

// one event of a dump, as text: from its name to the next event's
static std::string EventText(const std::string& dump, const std::string& name) {
  const size_t at = dump.find("{\"name\":\"" + name + "\"");
  if (at == std::string::npos) return std::string();
  const size_t next = dump.find("{\"name\":", at + 1);
  return dump.substr(at, next == std::string::npos ? next : next - at);
}

TESTCASE(span_lineage_needs_no_trace_context) {
  telemetry::SetTraceContext(0, 0, -1);
  telemetry::TraceStart();
  {
    telemetry::ScopedSpan own("test.own_lineage");
    own.set_lineage(42);  // known once the body has run
  }
  {
    // the thread's: what a worker sets around a callee that records spans
    telemetry::ScopedLineage of_chunk((int64_t{3} << 32) | 5);
    { telemetry::ScopedSpan inner("test.thread_lineage"); }
    {
      telemetry::ScopedLineage nested(7);
      telemetry::RecordSpan("test.nested_lineage", 1, 1);
    }
    telemetry::RecordSpan("test.back_to_outer", 1, 1);
    telemetry::RecordSpan("test.explicit_wins", 1, 1, 9);
  }
  telemetry::RecordSpan("test.no_lineage", 1, 1);
  std::thread([] {  // another thread does not see this thread's guard
    telemetry::RecordSpan("test.other_thread", 1, 1);
  }).join();
  EXPECT_EQV(DmlcTpuTelemetryRecordSpanLineage("test.c_api_lineage", 5, 6, 11),
             0);
  telemetry::TraceStop();
  const std::string js = telemetry::TraceDumpJson();
  WalkJson(js.c_str());
  if (!telemetry::Enabled()) return;
  auto lineage_of = [&](const char* name) {
    const std::string ev = EventText(js, name);
    EXPECT_TRUE(!ev.empty());
    const size_t at = ev.find("\"args\":{\"lineage\":");
    if (at == std::string::npos) return int64_t{-1};
    EXPECT_TRUE(ev.find("trace_id") == std::string::npos);
    return static_cast<int64_t>(std::atoll(ev.c_str() + at + 18));
  };
  EXPECT_EQV(lineage_of("test.own_lineage"), int64_t{42});
  EXPECT_EQV(lineage_of("test.thread_lineage"), (int64_t{3} << 32) | 5);
  EXPECT_EQV(lineage_of("test.nested_lineage"), int64_t{7});
  EXPECT_EQV(lineage_of("test.back_to_outer"), (int64_t{3} << 32) | 5);
  EXPECT_EQV(lineage_of("test.explicit_wins"), int64_t{9});
  EXPECT_EQV(lineage_of("test.no_lineage"), int64_t{-1});
  EXPECT_EQV(lineage_of("test.other_thread"), int64_t{-1});
  EXPECT_EQV(lineage_of("test.c_api_lineage"), int64_t{11});
}

TESTCASE(spans_not_recorded_while_inactive) {
  telemetry::TraceStart();
  telemetry::TraceStop();
  { telemetry::ScopedSpan s("test.after_stop"); }
  for (const auto& ev : ParseTrace(telemetry::TraceDumpJson())) {
    EXPECT_TRUE(ev.name != "test.after_stop");
  }
}

TESTCASE(c_api_span_keeps_its_total_tracing_on_or_off) {
  // DmlcTpuTelemetryRecordSpanTotal: the ring's event only while tracing,
  // the span's counter and main.span_us either way, one call
  int64_t total0 = 0, main0 = 0, total1 = 0, main1 = 0;
  DmlcTpuTelemetryCounterGet("test.span_total_us", &total0);
  DmlcTpuTelemetryCounterGet("main.span_us", &main0);
  telemetry::TraceStart();
  telemetry::TraceStop();
  EXPECT_EQV(DmlcTpuTelemetryRecordSpanTotal("test.off", 5, 40, -1,
                                             "test.span_total_us", 1), 0);
  telemetry::TraceStart();
  EXPECT_EQV(DmlcTpuTelemetryRecordSpanTotal("test.on", 50, 2, 7,
                                             "test.span_total_us", 0), 0);
  EXPECT_EQV(DmlcTpuTelemetryRecordSpanTotal("test.bare", 60, 9, -1, nullptr,
                                             0), 0);
  telemetry::TraceStop();
  DmlcTpuTelemetryCounterGet("test.span_total_us", &total1);
  DmlcTpuTelemetryCounterGet("main.span_us", &main1);
  const std::string js = telemetry::TraceDumpJson();
  WalkJson(js.c_str());
  if (!telemetry::Enabled()) return;
  EXPECT_EQV(total1 - total0, int64_t{42});
  EXPECT_EQV(main1 - main0, int64_t{40});
  EXPECT_TRUE(EventText(js, "test.off").empty());
  EXPECT_TRUE(!EventText(js, "test.on").empty());
  EXPECT_TRUE(!EventText(js, "test.bare").empty());
}

TESTCASE(snapshot_during_active_pipeline) {
  TemporaryDirectory tmp;
  std::string f = MakeLibsvm(tmp.path, 20000);
  auto* reg = telemetry::Registry::Get();
  bool before_enabled = false;
  std::map<std::string, int64_t> before_c, before_g;
  ParseSnapshot(reg->SnapshotJson(), &before_enabled, &before_c, &before_g);

  telemetry::TraceStart();
  std::atomic<bool> done{false};
  std::atomic<size_t> rows{0};
  std::thread consumer([&] {
    std::string uri = f + "?nthread=2";
    auto parser = Parser<uint32_t>::Create(uri.c_str(), 0, 1, "libsvm");
    size_t n = 0;
    while (parser->Next()) n += parser->Value().size;
    rows.store(n);
    done.store(true);
  });
  // hammer snapshots + a counter while the parse pool runs: the registry
  // must stay readable and every snapshot must stay well-formed JSON
  size_t snapshots = 0;
  while (!done.load()) {
    WalkJson(reg->SnapshotJson());
    reg->counter("test.during_pipeline").Add(1);
    ++snapshots;
  }
  consumer.join();
  telemetry::TraceStop();
  EXPECT_TRUE(snapshots > 0);
  EXPECT_EQV(rows.load(), 20000u);

  std::map<std::string, int64_t> after_c, after_g;
  ParseSnapshot(reg->SnapshotJson(), &before_enabled, &after_c, &after_g);
  if (telemetry::Enabled()) {
    EXPECT_TRUE(after_c["parse.rows"] - before_c["parse.rows"] == 20000);
    EXPECT_TRUE(after_c["parse.nnz"] - before_c["parse.nnz"] == 60000);
    EXPECT_TRUE(after_c["parse.busy_us"] >= before_c["parse.busy_us"]);
    EXPECT_TRUE(after_c["split.bytes"] > before_c["split.bytes"]);
    WalkJson(telemetry::TraceDumpJson());
  }
}

TESTCASE(instrumentation_bit_identity) {
  // tracing on vs off must not change parse output (same-build half of the
  // guard; the DMLCTPU_TELEMETRY=0 check.sh tier re-runs this whole suite
  // plus test_data against the stubbed build for the cross-build half)
  TemporaryDirectory tmp;
  std::string f = MakeLibsvm(tmp.path, 5000);
  auto drain = [&] {
    auto parser = Parser<uint32_t>::Create(f.c_str(), 0, 1, "libsvm");
    data::RowBlockContainer<uint32_t> all;
    while (parser->Next()) all.Push(parser->Value());
    return all;
  };
  auto plain = drain();
  telemetry::TraceStart();
  auto traced = drain();
  telemetry::TraceStop();
  EXPECT_EQV(plain.Size(), traced.Size());
  EXPECT_TRUE(plain.offset == traced.offset);
  EXPECT_TRUE(plain.label == traced.label);
  EXPECT_TRUE(plain.index == traced.index);
  EXPECT_TRUE(std::memcmp(plain.value.data(), traced.value.data(),
                          plain.value.size() * sizeof(float)) == 0);
}

TESTCASE(c_api_telemetry_surface) {
  int enabled = -1;
  EXPECT_EQV(DmlcTpuTelemetryEnabled(&enabled), 0);
  EXPECT_EQV(enabled, telemetry::Enabled() ? 1 : 0);

  EXPECT_EQV(DmlcTpuTelemetryCounterAdd("test.c_api_counter", 17), 0);
  int64_t v = -1;
  EXPECT_EQV(DmlcTpuTelemetryCounterGet("test.c_api_counter", &v), 0);
  if (telemetry::Enabled()) EXPECT_TRUE(v >= 17);

  const char* js = nullptr;
  EXPECT_EQV(DmlcTpuTelemetrySnapshotJson(&js), 0);
  EXPECT_TRUE(js != nullptr);
  WalkJson(js);

  EXPECT_EQV(DmlcTpuTelemetryTraceStart(), 0);
  EXPECT_EQV(DmlcTpuTelemetryRecordSpan("test.c_api_span", 1000, 20), 0);
  EXPECT_EQV(DmlcTpuTelemetryTraceStop(), 0);
  EXPECT_EQV(DmlcTpuTelemetryTraceDumpJson(&js), 0);
  auto events = ParseTrace(js);
  if (telemetry::Enabled()) {
    EXPECT_EQV(events.size(), 1u);
    EXPECT_EQV(events[0].name, std::string("test.c_api_span"));
    EXPECT_EQV(events[0].ts, int64_t{1000});
    EXPECT_EQV(events[0].dur, int64_t{20});
  }
}

namespace {
std::vector<std::pair<int, std::string>>& CapturedLogs() {
  static std::vector<std::pair<int, std::string>> logs;
  return logs;
}
extern "C" void TestLogCallback(int severity, const char* where,
                                const char* message) {
  (void)where;
  CapturedLogs().emplace_back(severity, message);
}
}  // namespace

TESTCASE(log_callback_capture) {
  CapturedLogs().clear();
  EXPECT_EQV(DmlcTpuLogSetCallback(&TestLogCallback), 0);
  TLOG(Warning) << "captured warning";
  EXPECT_EQV(DmlcTpuLogEmit(3, "captured error"), 0);
  EXPECT_EQV(DmlcTpuLogEmit(99, "clamped to error"), 0);  // never FATAL
  EXPECT_EQV(DmlcTpuLogSetCallback(nullptr), 0);  // restore stderr sink
  TLOG(Info) << "not captured (sink removed)";

  EXPECT_EQV(CapturedLogs().size(), 3u);
  EXPECT_EQV(CapturedLogs()[0].first, 2);
  EXPECT_EQV(CapturedLogs()[0].second, std::string("captured warning"));
  EXPECT_EQV(CapturedLogs()[1].first, 3);
  EXPECT_EQV(CapturedLogs()[1].second, std::string("captured error"));
  EXPECT_EQV(CapturedLogs()[2].first, 3);
}

TESTCASE(log_sink_swap_under_concurrent_emits) {
  // SetSink copies the sink under a mutex before invoking: swapping sinks
  // while worker threads log must neither crash nor deadlock
  std::atomic<bool> stop{false};
  std::atomic<int> seen{0};
  std::vector<std::thread> ts;
  for (int t = 0; t < 3; ++t) {
    ts.emplace_back([&] {
      while (!stop.load()) TLOG(Warning) << "spin";
    });
  }
  for (int i = 0; i < 200; ++i) {
    log::SetSink([&seen](LogSeverity, const char*, const std::string&) {
      seen.fetch_add(1);
    });
    log::SetSink([](LogSeverity, const char*, const std::string&) {});
  }
  log::SetSink([&seen](LogSeverity, const char*, const std::string&) {
    seen.fetch_add(1);
  });
  // let the workers hit the final sink at least once before stopping
  while (seen.load() == 0) std::this_thread::yield();
  stop.store(true);
  for (auto& t : ts) t.join();
  log::SetSink(log::Sink());
  EXPECT_TRUE(seen.load() > 0);
}

TESTCASE(snapshot_capture_and_merge_conservative) {
  using telemetry::Snapshot;
  auto* reg = telemetry::Registry::Get();
  reg->counter("test.merge_counter").Reset();
  reg->counter("test.merge_counter").Add(5);
  reg->gauge("test.merge_gauge").Set(3);
  reg->histogram("test.merge_hist").Reset();
  reg->histogram("test.merge_hist").Observe(3);  // bucket 2 (upper bound 4)
  Snapshot a = Snapshot::Capture();
  if (!telemetry::Enabled()) {
    EXPECT_TRUE(a.counters.empty());
    EXPECT_EQV(a.ToJson(), std::string("{\"enabled\":false}"));
    Snapshot empty;
    a.Merge(empty);  // stubbed no-op must not crash
    return;
  }
  EXPECT_EQV(a.counters.at("test.merge_counter"), uint64_t{5});
  EXPECT_EQV(a.gauges.at("test.merge_gauge"), int64_t{3});
  EXPECT_EQV(a.histograms.at("test.merge_hist").count, 1u);
  WalkJson(a.ToJson());

  // a second "host": Merge is pure struct arithmetic, exactly what the
  // tracker does across worker snapshots, so build it by hand
  Snapshot b;
  b.counters["test.merge_counter"] = 7;
  b.counters["test.merge_only_b"] = 2;
  b.gauges["test.merge_gauge"] = 4;
  Snapshot::Hist hb;
  hb.count = 1;
  hb.sum = 100;
  hb.buckets[7] = 1;  // 100 lands in bucket 7 (upper bound 128)
  b.histograms["test.merge_hist"] = hb;

  Snapshot m = a;
  m.Merge(b);
  EXPECT_EQV(m.counters.at("test.merge_counter"), uint64_t{12});
  EXPECT_EQV(m.counters.at("test.merge_only_b"), uint64_t{2});
  EXPECT_EQV(m.gauges.at("test.merge_gauge"), int64_t{7});
  const Snapshot::Hist& mh = m.histograms.at("test.merge_hist");
  EXPECT_EQV(mh.count, 2u);
  EXPECT_EQV(mh.sum, 103u);
  EXPECT_EQV(mh.buckets[2], 1u);
  EXPECT_EQV(mh.buckets[7], 1u);
  WalkJson(m.ToJson());

  // merged quantile estimates stay conservative: each merged bucket keeps
  // its upper bound, so the estimate never underestimates the true value
  auto quantile_ub = [](const Snapshot::Hist& h, double q) -> double {
    uint64_t target = static_cast<uint64_t>(q * static_cast<double>(h.count));
    if (target < 1) target = 1;
    uint64_t cum = 0;
    for (int i = 0; i < telemetry::Histogram::kBuckets; ++i) {
      cum += h.buckets[i];
      if (cum >= target) return std::pow(2.0, i);
    }
    return std::numeric_limits<double>::infinity();
  };
  // true merged observations are {3, 100}: median 3, max 100
  EXPECT_TRUE(quantile_ub(mh, 0.5) >= 3.0);
  EXPECT_TRUE(quantile_ub(mh, 1.0) >= 100.0);
}

TESTCASE(watchdog_no_false_positive_while_progressing) {
  telemetry::WatchdogOptions opts;
  opts.deadline_ms = 600;
  opts.poll_ms = 25;
  telemetry::WatchdogStart(opts);
  if (!telemetry::Enabled()) {
    EXPECT_TRUE(!telemetry::WatchdogRunning());
    EXPECT_EQV(telemetry::WatchdogStallCount(), 0u);
    telemetry::WatchdogStop();
    return;
  }
  EXPECT_TRUE(telemetry::WatchdogRunning());
  const uint64_t stalls0 = telemetry::WatchdogStallCount();
  telemetry::Counter& c = telemetry::Registry::Get()->counter("parse.rows");
  // slow but steady: a tick every ~100 ms never hits the 600 ms deadline
  for (int i = 0; i < 8; ++i) {
    c.Add(1);
    std::this_thread::sleep_for(std::chrono::milliseconds(100));
  }
  EXPECT_EQV(telemetry::WatchdogStallCount(), stalls0);
  telemetry::WatchdogStop();
  EXPECT_TRUE(!telemetry::WatchdogRunning());
}

TESTCASE(watchdog_stall_dumps_flight_record) {
  TemporaryDirectory tmp;
  const std::string dump = tmp.path + "/flight.json";
  telemetry::WatchdogOptions opts;
  opts.deadline_ms = 150;
  opts.poll_ms = 25;
  opts.abort_on_stall = false;  // warn policy: log + dump, keep running
  opts.dump_path = dump;

  std::atomic<int> stall_logs{0};
  log::SetSink([&stall_logs](LogSeverity, const char* where,
                             const std::string& msg) {
    // the sink's `where` is "file:line"; the watchdog emits as "watchdog:0"
    if (std::string(where).rfind("watchdog", 0) == 0 &&
        msg.find("pipeline stall") != std::string::npos) {
      stall_logs.fetch_add(1);
    }
  });

  const uint64_t stalls0 = telemetry::WatchdogStallCount();
  telemetry::WatchdogStart(opts);
  if (telemetry::Enabled()) {
    // march exactly one stage forward so the record can name it, then
    // wedge: h2d emitted its last batch and nothing moved afterwards
    telemetry::Registry::Get()->counter("h2d.batches").Add(1);
    for (int i = 0;
         i < 200 && telemetry::WatchdogStallCount() == stalls0; ++i) {
      std::this_thread::sleep_for(std::chrono::milliseconds(25));
    }
    EXPECT_TRUE(telemetry::WatchdogStallCount() > stalls0);
  }
  telemetry::WatchdogStop();
  log::SetSink(log::Sink());

  if (!telemetry::Enabled()) {
    EXPECT_EQV(telemetry::LastFlightRecordJson(), std::string());
    WalkJson(telemetry::FlightRecordJson("manual"));  // {"enabled":false}
    return;
  }
  const std::string rec = telemetry::LastFlightRecordJson();
  WalkJson(rec);
  EXPECT_TRUE(rec.find("\"stalled_stage\":\"h2d\"") != std::string::npos);
  EXPECT_TRUE(rec.find("\"registry\":") != std::string::npos);
  EXPECT_TRUE(rec.find("\"trace\":") != std::string::npos);
  EXPECT_TRUE(stall_logs.load() >= 1);

  std::ifstream f(dump);
  std::stringstream ss;
  ss << f.rdbuf();
  WalkJson(ss.str());
  EXPECT_TRUE(ss.str().find("\"stalled_stage\":\"h2d\"") != std::string::npos);

  // a manual flight record while unarmed is still well-formed (ages -1)
  WalkJson(telemetry::FlightRecordJson("manual"));
}

TESTMAIN()
