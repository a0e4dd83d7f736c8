// C API implementation: exception → error-string translation at the boundary.
#include "dmlctpu/c_api.h"

#include <cstdlib>
#include <cstring>
#include <memory>
#include <sstream>
#include <string>
#include <vector>

#include "./data/binned_cache.h"
#include "./data/record_batcher.h"
#include "./data/sharded_parser.h"
#include "./data/staged_batcher.h"
#include "dmlctpu/data.h"
#include "dmlctpu/fault.h"
#include "dmlctpu/input_split.h"
#include "dmlctpu/io/filesystem.h"
#include "dmlctpu/json.h"
#include "dmlctpu/logging.h"
#include "dmlctpu/recordio.h"
#include "dmlctpu/stream.h"
#include "dmlctpu/telemetry.h"
#include "dmlctpu/timeseries.h"
#include "dmlctpu/watchdog.h"

namespace {

thread_local std::string last_error;

template <typename Fn>
int Guard(Fn&& fn) {
  try {
    return std::forward<Fn>(fn)();
  } catch (const std::exception& e) {
    last_error = e.what();
    return -1;
  } catch (...) {
    last_error = "unknown native error";
    return -1;
  }
}

struct ParserCtx {
  std::unique_ptr<dmlctpu::Parser<uint64_t, float>> parser;
};
struct StreamCtx {
  std::unique_ptr<dmlctpu::Stream> stream;
  dmlctpu::SeekStream* seekable = nullptr;  // non-owning view when seekable
};
struct SplitCtx {
  std::unique_ptr<dmlctpu::InputSplit> split;
};
struct WriterCtx {
  std::unique_ptr<dmlctpu::Stream> stream;
  std::unique_ptr<dmlctpu::RecordIOWriter> writer;
};
struct ReaderCtx {
  std::unique_ptr<dmlctpu::Stream> stream;
  std::unique_ptr<dmlctpu::RecordIOReader> reader;
  std::string record;
};
struct BatcherCtx {
  std::unique_ptr<dmlctpu::data::StagedBatcher> batcher;
  // backs the borrowed DmlcTpuStagedBatcherNext view until the next call
  dmlctpu::data::OwnedStagedBatch borrowed;
  uint64_t batch_size = 0;
};
struct RecordBatcherCtx {
  std::unique_ptr<dmlctpu::data::RecordBatcher> batcher;
  dmlctpu::data::RecordBatch* borrowed = nullptr;
  uint64_t records_cap = 0;
  uint64_t bytes_cap = 0;
};
struct BinnedCacheWriterCtx {
  std::unique_ptr<dmlctpu::data::BinnedCacheWriter> writer;
};
struct BinnedCacheReaderCtx {
  std::unique_ptr<dmlctpu::data::BinnedCacheReader> reader;
  std::string block;  // backs the borrowed NextBlock view until the next call
};

// num_workers > 1 → parallel sharded parse pool; num_workers < 0 → sharded
// pool with |num_workers| workers even when that is 1 (autotuner arming: a
// 1-worker pool emits the same stream as the single-stream reader but stays
// live-retunable via SetPoolKnobs); otherwise the plain single-stream
// parser, so the default single-worker path stays bit-identical to the V1
// entry points
template <typename IndexType>
std::unique_ptr<dmlctpu::Parser<IndexType, float>> MakeParser(
    const char* uri, unsigned part, unsigned num_parts, const char* format,
    int num_workers, int reorder, uint64_t buffer_bytes) {
  if (num_workers > 1 || num_workers < 0) {
    int nw = num_workers < 0 ? std::max(-num_workers, 1) : num_workers;
    size_t buf = buffer_bytes != 0
        ? static_cast<size_t>(buffer_bytes)
        : dmlctpu::data::ShardedParser<IndexType, float>::kDefaultBufferBytes;
    return std::make_unique<dmlctpu::data::ShardedParser<IndexType, float>>(
        uri, part, num_parts, format, nw, reorder != 0, buf);
  }
  return dmlctpu::Parser<IndexType, float>::Create(uri, part, num_parts, format);
}

// retune when the parser is a sharded pool; single-stream parsers report
// applied = 0 and the call is a no-op (the autotuner treats those knobs as
// next-epoch-only)
template <typename IndexType>
int SetPoolKnobsOn(dmlctpu::Parser<IndexType, float>* p, int num_workers,
                   uint64_t buffer_bytes, uint64_t chunk_bytes) {
  auto* sharded =
      dynamic_cast<dmlctpu::data::ShardedParser<IndexType, float>*>(p);
  if (sharded == nullptr) return 0;
  sharded->SetPoolKnobs(num_workers, static_cast<size_t>(buffer_bytes),
                        static_cast<size_t>(chunk_bytes));
  return 1;
}

template <typename IndexType>
int GetPoolKnobsOn(dmlctpu::Parser<IndexType, float>* p, int* num_workers,
                   uint64_t* buffer_bytes, uint64_t* chunk_bytes) {
  auto* sharded =
      dynamic_cast<dmlctpu::data::ShardedParser<IndexType, float>*>(p);
  if (sharded == nullptr) return 0;
  *num_workers = sharded->pool_workers();
  *buffer_bytes = static_cast<uint64_t>(sharded->pool_buffer_bytes());
  *chunk_bytes = static_cast<uint64_t>(sharded->pool_chunk_bytes());
  return 1;
}

}  // namespace

extern "C" {

const char* DmlcTpuGetLastError(void) { return last_error.c_str(); }
const char* DmlcTpuVersion(void) { return "0.1.0"; }

/* ---- telemetry ----------------------------------------------------------- */

namespace {
// returned pointers stay valid until the next telemetry call on the same
// thread (same contract as fs_listing above)
thread_local std::string telemetry_json;
}  // namespace

int DmlcTpuTelemetryEnabled(int* out) {
  return Guard([&] {
    *out = dmlctpu::telemetry::Enabled() ? 1 : 0;
    return 0;
  });
}

int DmlcTpuTelemetrySnapshotJson(const char** out) {
  return Guard([&] {
    telemetry_json = dmlctpu::telemetry::Registry::Get()->SnapshotJson();
    *out = telemetry_json.c_str();
    return 0;
  });
}

int DmlcTpuTelemetryReset(void) {
  return Guard([&] {
    dmlctpu::telemetry::Registry::Get()->ResetAll();
    return 0;
  });
}

int DmlcTpuTelemetryCounterAdd(const char* name, int64_t delta) {
  return Guard([&] {
    if (delta > 0) {
      dmlctpu::telemetry::Registry::Get()->counter(name).Add(
          static_cast<uint64_t>(delta));
    }
    return 0;
  });
}

int DmlcTpuTelemetryCounterGet(const char* name, int64_t* out) {
  return Guard([&] {
    *out = static_cast<int64_t>(
        dmlctpu::telemetry::Registry::Get()->counter(name).Value());
    return 0;
  });
}

int DmlcTpuTelemetryTraceStart(void) {
  return Guard([&] {
    dmlctpu::telemetry::TraceStart();
    return 0;
  });
}

int DmlcTpuTelemetryTraceStop(void) {
  return Guard([&] {
    dmlctpu::telemetry::TraceStop();
    return 0;
  });
}

int DmlcTpuTelemetryTraceDumpJson(const char** out) {
  return Guard([&] {
    telemetry_json = dmlctpu::telemetry::TraceDumpJson();
    *out = telemetry_json.c_str();
    return 0;
  });
}

int DmlcTpuTelemetryRecordSpan(const char* name, int64_t ts_us,
                               int64_t dur_us) {
  return DmlcTpuTelemetryRecordSpanLineage(name, ts_us, dur_us, -1);
}

int DmlcTpuTelemetryRecordSpanLineage(const char* name, int64_t ts_us,
                                      int64_t dur_us, int64_t lineage) {
  return DmlcTpuTelemetryRecordSpanTotal(name, ts_us, dur_us, lineage,
                                         nullptr, 0);
}

int DmlcTpuTelemetryRecordSpanTotal(const char* name, int64_t ts_us,
                                    int64_t dur_us, int64_t lineage,
                                    const char* total, int main_outermost) {
  return Guard([&] {
    namespace tel = dmlctpu::telemetry;
    if (tel::TraceActive()) {
      tel::RecordSpanOwned(name, ts_us, dur_us, lineage);
    }
    if (dur_us > 0) {
      if (total != nullptr && total[0] != '\0') {
        tel::Registry::Get()->counter(total).Add(
            static_cast<uint64_t>(dur_us));
      }
      if (main_outermost != 0) {
        tel::stage::MainSpanUs().Add(static_cast<uint64_t>(dur_us));
      }
    }
    return 0;
  });
}

int DmlcTpuTelemetryGaugeSet(const char* name, int64_t value) {
  return Guard([&] {
    dmlctpu::telemetry::Registry::Get()->gauge(name).Set(value);
    return 0;
  });
}

int DmlcTpuTelemetryGaugeAdd(const char* name, int64_t delta) {
  return Guard([&] {
    dmlctpu::telemetry::Registry::Get()->gauge(name).Add(delta);
    return 0;
  });
}

int DmlcTpuTelemetryGaugeGet(const char* name, int64_t* out) {
  return Guard([&] {
    *out = dmlctpu::telemetry::Registry::Get()->gauge(name).Value();
    return 0;
  });
}

int DmlcTpuTelemetrySetTraceContext(uint64_t trace_id, uint64_t parent_span,
                                    int64_t lineage) {
  return Guard([&] {
    dmlctpu::telemetry::SetTraceContext(trace_id, parent_span, lineage);
    return 0;
  });
}

int DmlcTpuTelemetryGetTraceContext(uint64_t* trace_id, uint64_t* parent_span,
                                    int64_t* lineage) {
  return Guard([&] {
    dmlctpu::telemetry::GetTraceContext(trace_id, parent_span, lineage);
    return 0;
  });
}

int DmlcTpuJsonValidate(const char* json, int* out_ok) {
  return Guard([&] {
    *out_ok = 0;
    try {
      std::istringstream is(json == nullptr ? "" : json);
      dmlctpu::JSONReader reader(&is);
      reader.SkipValue();
      // one value, then nothing but whitespace
      char c;
      while (is.get(c)) {
        if (c != ' ' && c != '\t' && c != '\n' && c != '\r') {
          return 0;
        }
      }
      *out_ok = 1;
    } catch (const std::exception&) {
      *out_ok = 0;  // malformed input is a *result*, not an API failure
    }
    return 0;
  });
}

/* ---- watchdog / flight recorder ------------------------------------------- */

int DmlcTpuWatchdogStart(int64_t deadline_ms, int64_t poll_ms,
                         int abort_on_stall, const char* dump_path) {
  return Guard([&] {
    dmlctpu::telemetry::WatchdogOptions opts;
    opts.deadline_ms = deadline_ms;
    opts.poll_ms = poll_ms;
    opts.abort_on_stall = abort_on_stall != 0;
    opts.dump_path = dump_path == nullptr ? "" : dump_path;
    dmlctpu::telemetry::WatchdogStart(opts);
    return 0;
  });
}

int DmlcTpuWatchdogStop(void) {
  return Guard([&] {
    dmlctpu::telemetry::WatchdogStop();
    return 0;
  });
}

int DmlcTpuWatchdogRunning(int* out) {
  return Guard([&] {
    *out = dmlctpu::telemetry::WatchdogRunning() ? 1 : 0;
    return 0;
  });
}

int DmlcTpuWatchdogStallCount(int64_t* out) {
  return Guard([&] {
    *out = static_cast<int64_t>(dmlctpu::telemetry::WatchdogStallCount());
    return 0;
  });
}

int DmlcTpuFlightRecordJson(const char* reason, const char** out) {
  return Guard([&] {
    telemetry_json = dmlctpu::telemetry::FlightRecordJson(
        reason == nullptr ? "" : reason);
    *out = telemetry_json.c_str();
    return 0;
  });
}

int DmlcTpuWatchdogLastRecordJson(const char** out) {
  return Guard([&] {
    telemetry_json = dmlctpu::telemetry::LastFlightRecordJson();
    *out = telemetry_json.c_str();
    return 0;
  });
}

/* ---- time-series sampler -------------------------------------------------- */

int DmlcTpuTimeseriesStart(int64_t tick_ms, int64_t fine_slots,
                           int64_t coarse_every, int64_t coarse_slots) {
  return Guard([&] {
    dmlctpu::telemetry::TimeseriesOptions opts;
    opts.tick_ms = tick_ms;
    opts.fine_slots = fine_slots;
    opts.coarse_every = coarse_every;
    opts.coarse_slots = coarse_slots;
    dmlctpu::telemetry::TimeseriesStart(opts);
    return 0;
  });
}

int DmlcTpuTimeseriesStop(void) {
  return Guard([&] {
    dmlctpu::telemetry::TimeseriesStop();
    return 0;
  });
}

int DmlcTpuTimeseriesActive(int* out) {
  return Guard([&] {
    *out = dmlctpu::telemetry::TimeseriesActive() ? 1 : 0;
    return 0;
  });
}

int DmlcTpuTimeseriesSample(void) {
  return Guard([&] {
    dmlctpu::telemetry::TimeseriesSample();
    return 0;
  });
}

int DmlcTpuTimeseriesJson(const char** out) {
  return Guard([&] {
    telemetry_json = dmlctpu::telemetry::TimeseriesJson();
    *out = telemetry_json.c_str();
    return 0;
  });
}

int DmlcTpuTimeseriesTailJson(int points, const char** out) {
  return Guard([&] {
    telemetry_json = dmlctpu::telemetry::TimeseriesTailJson(points);
    *out = telemetry_json.c_str();
    return 0;
  });
}

/* ---- deterministic fault injection --------------------------------------- */

int DmlcTpuFaultCompiledIn(int* out) {
  return Guard([&] {
    *out = dmlctpu::fault::Enabled() ? 1 : 0;
    return 0;
  });
}

int DmlcTpuFaultArm(const char* spec) {
  return Guard([&] {
    std::string err;
    if (!dmlctpu::fault::ArmSpec(spec == nullptr ? "" : spec, &err)) {
      throw dmlctpu::Error(err);
    }
    return 0;
  });
}

int DmlcTpuFaultDisarm(void) {
  return Guard([&] {
    dmlctpu::fault::DisarmAll();
    return 0;
  });
}

int DmlcTpuFaultSnapshotJson(const char** out) {
  return Guard([&] {
    telemetry_json = dmlctpu::fault::SnapshotJson();
    *out = telemetry_json.c_str();
    return 0;
  });
}

int DmlcTpuFaultInjectedTotal(int64_t* out) {
  return Guard([&] {
    *out = static_cast<int64_t>(dmlctpu::fault::InjectedTotal());
    return 0;
  });
}

namespace {
// The dataservice client/worker hops live in Python but are hardened by this
// registry via DmlcTpuFaultFire; registering the points here keeps every
// armable name a DMLCTPU_FAULT_POINT site (doc/robustness.md contract).
void EnsureBindingFaultPoints() {
  DMLCTPU_FAULT_POINT(ds_connect, "dataservice.connect");
  DMLCTPU_FAULT_POINT(ds_drop, "dataservice.block.drop");
  DMLCTPU_FAULT_POINT(serve_snap_drop, "serving.snapshot.drop");
  DMLCTPU_FAULT_POINT(serve_malformed, "serving.request.malformed");
  (void)ds_connect;
  (void)ds_drop;
  (void)serve_snap_drop;
  (void)serve_malformed;
}
}  // namespace

int DmlcTpuFaultFire(const char* point, int* out_mode) {
  return Guard([&] {
    EnsureBindingFaultPoints();
    if (point == nullptr || *point == '\0') {
      throw dmlctpu::Error("DmlcTpuFaultFire: empty point name");
    }
    *out_mode = static_cast<int>(dmlctpu::fault::GetPoint(point).Fire());
    return 0;
  });
}

/* ---- logging ------------------------------------------------------------- */

int DmlcTpuLogSetCallback(DmlcTpuLogCallback callback) {
  return Guard([&] {
    if (callback == nullptr) {
      dmlctpu::log::SetSink(dmlctpu::log::Sink());
    } else {
      dmlctpu::log::SetSink([callback](dmlctpu::LogSeverity sev,
                                       const char* where,
                                       const std::string& msg) {
        callback(static_cast<int>(sev), where, msg.c_str());
      });
    }
    return 0;
  });
}

int DmlcTpuLogEmit(int severity, const char* message) {
  return Guard([&] {
    // clamp: FATAL throws natively and must not originate at the C boundary
    int sev = severity < 0 ? 0 : (severity > 3 ? 3 : severity);
    if (sev >= dmlctpu::log::MinLevel()) {
      dmlctpu::log::Emit(static_cast<dmlctpu::LogSeverity>(sev), "c_api", 0,
                         message == nullptr ? "" : message);
    }
    return 0;
  });
}

int DmlcTpuStreamCreate(const char* uri, const char* mode,
                        DmlcTpuStreamHandle* out) {
  return Guard([&] {
    auto ctx = std::make_unique<StreamCtx>();
    ctx->stream = dmlctpu::Stream::Create(uri, mode);
    *out = ctx.release();
    return 0;
  });
}

int64_t DmlcTpuStreamRead(DmlcTpuStreamHandle handle, void* buf, uint64_t n) {
  int64_t got = -1;
  int rc = Guard([&] {
    auto* ctx = static_cast<StreamCtx*>(handle);
    got = static_cast<int64_t>(ctx->stream->Read(buf, n));
    return 0;
  });
  return rc == 0 ? got : -1;
}

int DmlcTpuStreamWrite(DmlcTpuStreamHandle handle, const void* buf,
                       uint64_t n) {
  return Guard([&] {
    auto* ctx = static_cast<StreamCtx*>(handle);
    ctx->stream->Write(buf, n);
    return 0;
  });
}

int DmlcTpuStreamClose(DmlcTpuStreamHandle handle) {
  return Guard([&] {
    auto* ctx = static_cast<StreamCtx*>(handle);
    // the virtual Close() is the throwing flush (destructors deliberately
    // swallow — see S3WriteStream/StdioFileStream): errors like a failed
    // multipart completion or ENOSPC surface HERE, then the nothrow
    // destructor in Free is a no-op
    ctx->stream->Close();
    return 0;
  });
}

void DmlcTpuStreamFree(DmlcTpuStreamHandle handle) {
  delete static_cast<StreamCtx*>(handle);
}

int DmlcTpuSeekStreamCreate(const char* uri, DmlcTpuStreamHandle* out) {
  return Guard([&] {
    auto ctx = std::make_unique<StreamCtx>();
    auto seek = dmlctpu::SeekStream::CreateForRead(uri);
    ctx->seekable = seek.get();
    ctx->stream = std::move(seek);
    *out = ctx.release();
    return 0;
  });
}

int DmlcTpuStreamSeek(DmlcTpuStreamHandle handle, uint64_t pos) {
  return Guard([&] {
    auto* ctx = static_cast<StreamCtx*>(handle);
    TCHECK(ctx->seekable != nullptr)
        << "stream is not seekable (open it with SeekStreamCreate)";
    ctx->seekable->Seek(pos);
    return 0;
  });
}

int64_t DmlcTpuStreamTell(DmlcTpuStreamHandle handle) {
  int64_t pos = -1;
  int rc = Guard([&] {
    auto* ctx = static_cast<StreamCtx*>(handle);
    TCHECK(ctx->seekable != nullptr)
        << "stream is not seekable (open it with SeekStreamCreate)";
    pos = static_cast<int64_t>(ctx->seekable->Tell());
    return 0;
  });
  return rc == 0 ? pos : -1;
}

namespace {
thread_local std::string fs_listing;

void AppendFileInfo(const dmlctpu::io::FileInfo& info, std::string* out) {
  out->push_back(info.type == dmlctpu::io::FileType::kDirectory ? 'd' : 'f');
  out->push_back('\t');
  out->append(std::to_string(info.size));
  out->push_back('\t');
  // newline/tab are legal in POSIX filenames and object keys: escape them
  // so the line format stays parseable
  for (char c : info.path.str()) {
    switch (c) {
      case '\\': out->append("\\\\"); break;
      case '\n': out->append("\\n"); break;
      case '\t': out->append("\\t"); break;
      default: out->push_back(c);
    }
  }
  out->push_back('\n');
}
}  // namespace

int DmlcTpuFsListDirectory(const char* uri, int recursive, const char** out) {
  return Guard([&] {
    dmlctpu::io::URI parsed(uri);
    auto* fs = dmlctpu::io::FileSystem::GetInstance(parsed);
    std::vector<dmlctpu::io::FileInfo> entries;
    if (recursive != 0) {
      fs->ListDirectoryRecursive(parsed, &entries);
    } else {
      fs->ListDirectory(parsed, &entries);
    }
    fs_listing.clear();
    for (const auto& e : entries) AppendFileInfo(e, &fs_listing);
    *out = fs_listing.c_str();
    return 0;
  });
}

int DmlcTpuFsPathInfo(const char* uri, const char** out) {
  return Guard([&] {
    dmlctpu::io::URI parsed(uri);
    auto* fs = dmlctpu::io::FileSystem::GetInstance(parsed);
    fs_listing.clear();
    AppendFileInfo(fs->GetPathInfo(parsed), &fs_listing);
    *out = fs_listing.c_str();
    return 0;
  });
}

int DmlcTpuBinnedCacheWriterCreate(const char* uri, const char* meta_json,
                                   DmlcTpuBinnedCacheWriterHandle* out) {
  return Guard([&] {
    auto ctx = std::make_unique<BinnedCacheWriterCtx>();
    ctx->writer = std::make_unique<dmlctpu::data::BinnedCacheWriter>(
        uri, meta_json != nullptr ? meta_json : "");
    *out = ctx.release();
    return 0;
  });
}

int DmlcTpuBinnedCacheWriterWriteBlock(DmlcTpuBinnedCacheWriterHandle handle,
                                       uint32_t part_id, uint64_t rows,
                                       uint64_t nnz, const void* data,
                                       uint64_t size) {
  return Guard([&] {
    auto* ctx = static_cast<BinnedCacheWriterCtx*>(handle);
    ctx->writer->WriteBlock(part_id, rows, nnz, data,
                            static_cast<size_t>(size));
    return 0;
  });
}

int DmlcTpuBinnedCacheWriterSetCuts(DmlcTpuBinnedCacheWriterHandle handle,
                                    const float* cuts, uint64_t num_features,
                                    uint64_t num_cuts) {
  return Guard([&] {
    static_cast<BinnedCacheWriterCtx*>(handle)->writer->SetCuts(
        cuts, num_features, num_cuts);
    return 0;
  });
}

int DmlcTpuBinnedCacheWriterWriteRaw(DmlcTpuBinnedCacheWriterHandle handle,
                                     uint32_t part_id, uint32_t seq,
                                     uint64_t rows, uint64_t nnz,
                                     const float* label, const float* weight,
                                     const int32_t* row_ptr,
                                     const int32_t* index, const float* value,
                                     const int32_t* qid) {
  return Guard([&] {
    static_cast<BinnedCacheWriterCtx*>(handle)->writer->WriteRawBlock(
        part_id, seq, rows, nnz, label, weight, row_ptr, index, value, qid);
    return 0;
  });
}

int DmlcTpuBinnedCacheWriterClose(DmlcTpuBinnedCacheWriterHandle handle) {
  return Guard([&] {
    static_cast<BinnedCacheWriterCtx*>(handle)->writer->Close();
    return 0;
  });
}

void DmlcTpuBinnedCacheWriterFree(DmlcTpuBinnedCacheWriterHandle handle) {
  delete static_cast<BinnedCacheWriterCtx*>(handle);
}

int DmlcTpuBinnedCacheReaderCreate(const char* uri, int recover,
                                   DmlcTpuBinnedCacheReaderHandle* out) {
  return Guard([&] {
    auto ctx = std::make_unique<BinnedCacheReaderCtx>();
    ctx->reader = std::make_unique<dmlctpu::data::BinnedCacheReader>(
        uri, recover != 0);
    *out = ctx.release();
    return 0;
  });
}

int DmlcTpuBinnedCacheReaderValid(DmlcTpuBinnedCacheReaderHandle handle,
                                  int* out) {
  return Guard([&] {
    *out = static_cast<BinnedCacheReaderCtx*>(handle)->reader->valid() ? 1 : 0;
    return 0;
  });
}

int DmlcTpuBinnedCacheReaderMissing(DmlcTpuBinnedCacheReaderHandle handle,
                                    int* out) {
  return Guard([&] {
    *out =
        static_cast<BinnedCacheReaderCtx*>(handle)->reader->missing() ? 1 : 0;
    return 0;
  });
}

int DmlcTpuBinnedCacheReaderError(DmlcTpuBinnedCacheReaderHandle handle,
                                  const char** out) {
  return Guard([&] {
    *out = static_cast<BinnedCacheReaderCtx*>(handle)->reader->error().c_str();
    return 0;
  });
}

int DmlcTpuBinnedCacheReaderMetaJson(DmlcTpuBinnedCacheReaderHandle handle,
                                     const char** out) {
  return Guard([&] {
    *out =
        static_cast<BinnedCacheReaderCtx*>(handle)->reader->meta_json().c_str();
    return 0;
  });
}

int DmlcTpuBinnedCacheReaderPartMapJson(DmlcTpuBinnedCacheReaderHandle handle,
                                        const char** out) {
  return Guard([&] {
    *out = static_cast<BinnedCacheReaderCtx*>(handle)
               ->reader->part_map_json()
               .c_str();
    return 0;
  });
}

int DmlcTpuBinnedCacheReaderNextBlock(DmlcTpuBinnedCacheReaderHandle handle,
                                      const void** data, uint64_t* size) {
  return Guard([&] {
    auto* ctx = static_cast<BinnedCacheReaderCtx*>(handle);
    if (!ctx->reader->NextBlock(&ctx->block)) return 0;
    *data = ctx->block.data();
    *size = static_cast<uint64_t>(ctx->block.size());
    return 1;
  });
}

int DmlcTpuBinnedCacheReaderNextBlockView(
    DmlcTpuBinnedCacheReaderHandle handle, const void** data, uint64_t* size,
    int* borrowed) {
  return Guard([&] {
    auto* ctx = static_cast<BinnedCacheReaderCtx*>(handle);
    const char* d = nullptr;
    uint64_t n = 0;
    int b = 0;
    if (!ctx->reader->NextBlockView(&d, &n, &b)) return 0;
    *data = d;
    *size = n;
    *borrowed = b;
    return 1;
  });
}

int DmlcTpuBinnedCacheReaderBackend(DmlcTpuBinnedCacheReaderHandle handle,
                                    int* out) {
  return Guard([&] {
    *out = static_cast<int>(
        static_cast<BinnedCacheReaderCtx*>(handle)->reader->backend());
    return 0;
  });
}

int DmlcTpuBinnedCacheReaderSeekTo(DmlcTpuBinnedCacheReaderHandle handle,
                                   uint64_t offset) {
  return Guard([&] {
    static_cast<BinnedCacheReaderCtx*>(handle)->reader->SeekTo(offset);
    return 0;
  });
}

int DmlcTpuBinnedCacheReaderBeforeFirst(
    DmlcTpuBinnedCacheReaderHandle handle) {
  return Guard([&] {
    static_cast<BinnedCacheReaderCtx*>(handle)->reader->BeforeFirst();
    return 0;
  });
}

int64_t DmlcTpuBinnedCacheReaderCorruptSkipped(
    DmlcTpuBinnedCacheReaderHandle handle) {
  int64_t got = -1;
  int rc = Guard([&] {
    got = static_cast<int64_t>(
        static_cast<BinnedCacheReaderCtx*>(handle)->reader->corrupt_skipped());
    return 0;
  });
  return rc == 0 ? got : -1;
}

void DmlcTpuBinnedCacheReaderFree(DmlcTpuBinnedCacheReaderHandle handle) {
  delete static_cast<BinnedCacheReaderCtx*>(handle);
}

int DmlcTpuCacheArenaAcquire(uint64_t size, void** out) {
  return Guard([&] {
    *out = dmlctpu::data::CacheArenaPool::Get()->Acquire(
        static_cast<size_t>(size));
    return 0;
  });
}

int DmlcTpuCacheArenaRelease(void* ptr) {
  return Guard([&] {
    dmlctpu::data::CacheArenaPool::Get()->Release(ptr);
    return 0;
  });
}

int DmlcTpuBinnedCacheWriterSetCodec(DmlcTpuBinnedCacheWriterHandle handle,
                                     int codec) {
  return Guard([&] {
    static_cast<BinnedCacheWriterCtx*>(handle)->writer->SetCodec(codec);
    return 0;
  });
}

int DmlcTpuBinnedCacheReaderTakeArena(DmlcTpuBinnedCacheReaderHandle handle,
                                      void** out) {
  return Guard([&] {
    *out =
        static_cast<BinnedCacheReaderCtx*>(handle)->reader->TakeDecodeArena();
    return 0;
  });
}

int DmlcTpuBinnedCacheReaderSetDecode(DmlcTpuBinnedCacheReaderHandle handle,
                                      int decode) {
  return Guard([&] {
    static_cast<BinnedCacheReaderCtx*>(handle)->reader->SetDecode(decode != 0);
    return 0;
  });
}

int DmlcTpuBlockCodecEnabled(void) {
  return dmlctpu::codec::Enabled() ? 1 : 0;
}

int DmlcTpuBlockCodecFromName(const char* name) {
  return dmlctpu::codec::FromName(name);
}

const char* DmlcTpuBlockCodecName(int codec) {
  return dmlctpu::codec::Name(codec);
}

uint64_t DmlcTpuBlockCodecBound(uint64_t n) {
  return static_cast<uint64_t>(
      dmlctpu::codec::CompressBound(static_cast<size_t>(n)));
}

int64_t DmlcTpuBlockCodecEncode(int codec, const void* in, uint64_t n,
                                void* out, uint64_t cap) {
  int64_t got = -1;
  int rc = Guard([&] {
    got = static_cast<int64_t>(dmlctpu::codec::Compress(
        codec, static_cast<const uint8_t*>(in), static_cast<size_t>(n),
        static_cast<uint8_t*>(out), static_cast<size_t>(cap)));
    return 0;
  });
  return rc == 0 ? got : -1;
}

int64_t DmlcTpuBlockCodecDecode(int codec, const void* in, uint64_t n,
                                void* out, uint64_t raw_len) {
  int64_t got = -1;
  int rc = Guard([&] {
    got = dmlctpu::codec::Decompress(
              codec, static_cast<const uint8_t*>(in), static_cast<size_t>(n),
              static_cast<uint8_t*>(out), static_cast<size_t>(raw_len))
              ? 0
              : -1;
    return 0;
  });
  return rc == 0 ? got : -1;
}

int DmlcTpuBinnedBlockDecode(const void* payload, uint64_t size, void** arena,
                             uint64_t* out_size) {
  return Guard([&] {
    dmlctpu::data::BinnedCacheReader::DecodePayloadToArena(
        static_cast<const char*>(payload), size, arena, out_size);
    return 0;
  });
}

int DmlcTpuParserCreate(const char* uri, unsigned part, unsigned num_parts,
                        const char* format, DmlcTpuParserHandle* out) {
  return Guard([&] {
    auto ctx = std::make_unique<ParserCtx>();
    ctx->parser = dmlctpu::Parser<uint64_t, float>::Create(uri, part, num_parts, format);
    ctx->parser->BeforeFirst();
    *out = ctx.release();
    return 0;
  });
}

int DmlcTpuParserCreateEx(const char* uri, unsigned part, unsigned num_parts,
                          const char* format, int num_workers, int reorder,
                          uint64_t buffer_bytes, DmlcTpuParserHandle* out) {
  return Guard([&] {
    auto ctx = std::make_unique<ParserCtx>();
    ctx->parser = MakeParser<uint64_t>(uri, part, num_parts, format,
                                       num_workers, reorder, buffer_bytes);
    ctx->parser->BeforeFirst();
    *out = ctx.release();
    return 0;
  });
}

int DmlcTpuSetDefaultParseThreads(int nthread) {
  return Guard([&] {
    dmlctpu::data::SetDefaultParseThreads(nthread);
    return 0;
  });
}

int DmlcTpuGetDefaultParseThreads(int* out) {
  return Guard([&] {
    *out = dmlctpu::data::GetDefaultParseThreads();
    return 0;
  });
}

int DmlcTpuParserNext(DmlcTpuParserHandle handle, DmlcTpuRowBlockC* out) {
  return Guard([&] {
    auto* ctx = static_cast<ParserCtx*>(handle);
    if (!ctx->parser->Next()) return 0;
    const auto& b = ctx->parser->Value();
    out->size = b.size;
    out->offset = b.offset;
    out->label = b.label;
    out->weight = b.weight;
    out->qid = b.qid;
    out->field = b.field;
    out->index = b.index;
    out->value = b.value;
    return 1;
  });
}

int DmlcTpuParserBeforeFirst(DmlcTpuParserHandle handle) {
  return Guard([&] {
    static_cast<ParserCtx*>(handle)->parser->BeforeFirst();
    return 0;
  });
}

int64_t DmlcTpuParserBytesRead(DmlcTpuParserHandle handle) {
  return static_cast<int64_t>(static_cast<ParserCtx*>(handle)->parser->BytesRead());
}

int DmlcTpuParserSetPoolKnobs(DmlcTpuParserHandle handle, int num_workers,
                              uint64_t buffer_bytes, uint64_t chunk_bytes,
                              int* out_applied) {
  return Guard([&] {
    auto* ctx = static_cast<ParserCtx*>(handle);
    *out_applied = SetPoolKnobsOn<uint64_t>(ctx->parser.get(), num_workers,
                                            buffer_bytes, chunk_bytes);
    return 0;
  });
}

void DmlcTpuParserFree(DmlcTpuParserHandle handle) {
  delete static_cast<ParserCtx*>(handle);
}

int DmlcTpuInputSplitCreate(const char* uri, const char* index_uri, unsigned part,
                            unsigned num_parts, const char* type, int shuffle, int seed,
                            uint64_t batch_size, DmlcTpuInputSplitHandle* out) {
  return Guard([&] {
    auto ctx = std::make_unique<SplitCtx>();
    ctx->split = dmlctpu::InputSplit::Create(uri, index_uri, part, num_parts, type,
                                             shuffle != 0, seed, batch_size);
    *out = ctx.release();
    return 0;
  });
}

int DmlcTpuInputSplitNextRecord(DmlcTpuInputSplitHandle handle, const void** data,
                                uint64_t* size) {
  return Guard([&] {
    auto* ctx = static_cast<SplitCtx*>(handle);
    dmlctpu::InputSplit::Blob blob;
    if (!ctx->split->NextRecord(&blob)) return 0;
    *data = blob.dptr;
    *size = blob.size;
    return 1;
  });
}

int DmlcTpuInputSplitNextChunk(DmlcTpuInputSplitHandle handle, const void** data,
                               uint64_t* size) {
  return Guard([&] {
    auto* ctx = static_cast<SplitCtx*>(handle);
    dmlctpu::InputSplit::Blob blob;
    if (!ctx->split->NextChunk(&blob)) return 0;
    *data = blob.dptr;
    *size = blob.size;
    return 1;
  });
}

int DmlcTpuInputSplitBeforeFirst(DmlcTpuInputSplitHandle handle) {
  return Guard([&] {
    static_cast<SplitCtx*>(handle)->split->BeforeFirst();
    return 0;
  });
}

int DmlcTpuInputSplitResetPartition(DmlcTpuInputSplitHandle handle, unsigned part,
                                    unsigned num_parts) {
  return Guard([&] {
    static_cast<SplitCtx*>(handle)->split->ResetPartition(part, num_parts);
    return 0;
  });
}

int64_t DmlcTpuInputSplitTotalSize(DmlcTpuInputSplitHandle handle) {
  return static_cast<int64_t>(static_cast<SplitCtx*>(handle)->split->GetTotalSize());
}

void DmlcTpuInputSplitFree(DmlcTpuInputSplitHandle handle) {
  delete static_cast<SplitCtx*>(handle);
}

int DmlcTpuRecordIOWriterCreate(const char* uri, DmlcTpuRecordIOWriterHandle* out) {
  return Guard([&] {
    auto ctx = std::make_unique<WriterCtx>();
    ctx->stream = dmlctpu::Stream::Create(uri, "w");
    ctx->writer = std::make_unique<dmlctpu::RecordIOWriter>(ctx->stream.get());
    *out = ctx.release();
    return 0;
  });
}

int DmlcTpuRecordIOWriterWrite(DmlcTpuRecordIOWriterHandle handle, const void* data,
                               uint64_t size) {
  return Guard([&] {
    static_cast<WriterCtx*>(handle)->writer->WriteRecord(data, size);
    return 0;
  });
}

int DmlcTpuRecordIOWriterClose(DmlcTpuRecordIOWriterHandle handle) {
  return Guard([&] {
    static_cast<WriterCtx*>(handle)->stream->Close();
    return 0;
  });
}

void DmlcTpuRecordIOWriterFree(DmlcTpuRecordIOWriterHandle handle) {
  delete static_cast<WriterCtx*>(handle);
}

int DmlcTpuRecordIOReaderCreate(const char* uri, DmlcTpuRecordIOReaderHandle* out) {
  return DmlcTpuRecordIOReaderCreateEx(uri, 0, out);
}

int DmlcTpuRecordIOReaderCreateEx(const char* uri, int recover,
                                  DmlcTpuRecordIOReaderHandle* out) {
  return Guard([&] {
    auto ctx = std::make_unique<ReaderCtx>();
    ctx->stream = dmlctpu::Stream::Create(uri, "r");
    ctx->reader = std::make_unique<dmlctpu::RecordIOReader>(ctx->stream.get(),
                                                            recover != 0);
    *out = ctx.release();
    return 0;
  });
}

int64_t DmlcTpuRecordIOReaderCorruptSkipped(DmlcTpuRecordIOReaderHandle handle) {
  return static_cast<int64_t>(
      static_cast<ReaderCtx*>(handle)->reader->corrupt_skipped());
}

int DmlcTpuRecordIOReaderNext(DmlcTpuRecordIOReaderHandle handle, const void** data,
                              uint64_t* size) {
  return Guard([&] {
    auto* ctx = static_cast<ReaderCtx*>(handle);
    if (!ctx->reader->NextRecord(&ctx->record)) return 0;
    *data = ctx->record.data();
    *size = ctx->record.size();
    return 1;
  });
}

void DmlcTpuRecordIOReaderFree(DmlcTpuRecordIOReaderHandle handle) {
  delete static_cast<ReaderCtx*>(handle);
}

int DmlcTpuStagedBatcherCreate(const char* uri, unsigned part, unsigned num_parts,
                               const char* format, uint64_t batch_size,
                               uint64_t nnz_bucket, uint64_t nnz_max,
                               int with_field, int with_qid,
                               DmlcTpuStagedBatcherHandle* out) {
  return Guard([&] {
    auto ctx = std::make_unique<BatcherCtx>();
    // uint32 parse type: the staged device layout is int32, so the index
    // column packs with a straight memcpy (see staged_batcher.h)
    auto parser = dmlctpu::Parser<uint32_t, float>::Create(uri, part, num_parts, format);
    ctx->batcher = std::make_unique<dmlctpu::data::StagedBatcher>(
        std::move(parser), batch_size, nnz_bucket, with_field != 0, nnz_max,
        with_qid != 0);
    ctx->batch_size = batch_size;
    *out = ctx.release();
    return 0;
  });
}

int DmlcTpuStagedBatcherCreateEx(const char* uri, unsigned part,
                                 unsigned num_parts, const char* format,
                                 uint64_t batch_size, uint64_t nnz_bucket,
                                 uint64_t nnz_max, int with_field, int with_qid,
                                 int num_workers, int reorder,
                                 uint64_t buffer_bytes,
                                 DmlcTpuStagedBatcherHandle* out) {
  return Guard([&] {
    auto ctx = std::make_unique<BatcherCtx>();
    auto parser = MakeParser<uint32_t>(uri, part, num_parts, format,
                                       num_workers, reorder, buffer_bytes);
    ctx->batcher = std::make_unique<dmlctpu::data::StagedBatcher>(
        std::move(parser), batch_size, nnz_bucket, with_field != 0, nnz_max,
        with_qid != 0);
    ctx->batch_size = batch_size;
    *out = ctx.release();
    return 0;
  });
}

namespace {
void FillOwnedC(const dmlctpu::data::StagedArena* a, void* batch,
                DmlcTpuStagedBatchOwnedC* out) {
  out->num_rows = a->num_rows;
  out->batch_size = a->batch_size;
  out->nnz_pad = a->nnz_pad;
  out->max_index = a->max_index;
  out->batch = batch;
  out->arena = a->base;
  out->arena_bytes = a->bytes;
  out->label_off = a->label_off;
  out->weight_off = a->weight_off;
  out->row_ptr_off = a->row_ptr_off;
  out->index_off = a->index_off;
  out->value_off = a->value_off;
  out->field_off = a->with_field ? a->field_off : ~static_cast<uint64_t>(0);
  out->qid_off = a->with_qid ? a->qid_off : ~static_cast<uint64_t>(0);
  out->lineage = a->lineage;
}
}  // namespace

int DmlcTpuStagedBatcherNext(DmlcTpuStagedBatcherHandle handle, DmlcTpuStagedBatchC* out) {
  return Guard([&] {
    auto* ctx = static_cast<BatcherCtx*>(handle);
    if (!ctx->batcher->NextOwned(&ctx->borrowed)) {
      ctx->borrowed.Reset();
      return 0;
    }
    dmlctpu::data::StagedArena* a = ctx->borrowed.arena.get();
    out->num_rows = a->num_rows;
    out->batch_size = a->batch_size;
    out->nnz_pad = a->nnz_pad;
    out->max_index = a->max_index;
    out->label = a->label();
    out->weight = a->weight();
    out->row_ptr = a->row_ptr();
    out->index = a->index();
    out->value = a->value();
    out->field = a->with_field ? a->field() : nullptr;
    out->qid = a->with_qid ? a->qid() : nullptr;
    return 1;
  });
}

int DmlcTpuStagedBatcherNextOwned(DmlcTpuStagedBatcherHandle handle,
                                  DmlcTpuStagedBatchOwnedC* out) {
  return Guard([&] {
    auto* ctx = static_cast<BatcherCtx*>(handle);
    auto owned = std::make_unique<dmlctpu::data::OwnedStagedBatch>();
    if (!ctx->batcher->NextOwned(owned.get())) return 0;
    FillOwnedC(owned->arena.get(), owned.get(), out);
    owned.release();  // caller frees via DmlcTpuStagedBatchFree
    return 1;
  });
}

void DmlcTpuStagedBatchFree(void* batch) {
  // returns the arena to the batcher's pool (or frees it if the pool is full
  // or the batcher is gone — the pool is shared_ptr-held by each batch)
  delete static_cast<dmlctpu::data::OwnedStagedBatch*>(batch);
}

/* ---- staged-batch wire codec --------------------------------------------- */

namespace {

// Fixed native-endian wire header for one owned staged batch.  Native order
// matches the rest of the side-channel framing (struct "@i" in metrics.py);
// the magic word doubles as the cross-arch tripwire, exactly like the 0xff98
// handshake.  14 * 8 = 112 bytes == DMLCTPU_STAGED_WIRE_HEADER_BYTES.
// v2 appends the batch lineage id (the magic's low word is the version, so
// a v1 peer rejects a v2 stream loudly instead of misreading it).
struct StagedWireHeader {
  uint64_t magic;      // kStagedWireMagic
  uint64_t num_rows;   // widened from uint32 to keep the layout padding-free
  uint64_t batch_size;
  uint64_t nnz_pad;
  int64_t max_index;
  uint64_t arena_bytes;
  uint64_t label_off;
  uint64_t weight_off;
  uint64_t row_ptr_off;
  uint64_t index_off;
  uint64_t value_off;
  uint64_t field_off;
  uint64_t qid_off;
  int64_t lineage;     // (source virtual part << 32) | chunk index; -1 unknown
};
constexpr uint64_t kStagedWireMagic = 0xDB57A6ED00000002ULL;  // ..02 = v2
constexpr uint64_t kNoColumn = ~static_cast<uint64_t>(0);
static_assert(sizeof(StagedWireHeader) == DMLCTPU_STAGED_WIRE_HEADER_BYTES,
              "wire header layout drifted from the public constant");

// column span [off, off+len) must sit inside the arena (absent columns skip)
void CheckSpan(const char* what, uint64_t off, uint64_t len, uint64_t arena) {
  if (off == kNoColumn) return;
  if (off > arena || len > arena - off) {
    throw dmlctpu::Error(std::string("staged wire batch: column '") + what +
                         "' overruns the arena");
  }
}

}  // namespace

int DmlcTpuStagedBatchWireHeader(const DmlcTpuStagedBatchOwnedC* batch,
                                 void* buf, uint64_t cap, uint64_t* out_len) {
  return Guard([&] {
    if (cap < sizeof(StagedWireHeader)) {
      throw dmlctpu::Error("DmlcTpuStagedBatchWireHeader: buffer too small");
    }
    StagedWireHeader h{};
    h.magic = kStagedWireMagic;
    h.num_rows = batch->num_rows;
    h.batch_size = batch->batch_size;
    h.nnz_pad = batch->nnz_pad;
    h.max_index = batch->max_index;
    h.arena_bytes = batch->arena_bytes;
    h.label_off = batch->label_off;
    h.weight_off = batch->weight_off;
    h.row_ptr_off = batch->row_ptr_off;
    h.index_off = batch->index_off;
    h.value_off = batch->value_off;
    h.field_off = batch->field_off;
    h.qid_off = batch->qid_off;
    h.lineage = batch->lineage;
    std::memcpy(buf, &h, sizeof(h));
    *out_len = sizeof(h);
    return 0;
  });
}

int DmlcTpuStagedBatchFromWire(const void* header, uint64_t header_len,
                               void* arena, uint64_t arena_bytes,
                               DmlcTpuStagedBatchOwnedC* out) {
  return Guard([&] {
    if (header_len != sizeof(StagedWireHeader)) {
      throw dmlctpu::Error("staged wire batch: bad header length");
    }
    StagedWireHeader h;
    std::memcpy(&h, header, sizeof(h));
    if (h.magic != kStagedWireMagic) {
      throw dmlctpu::Error("staged wire batch: bad magic (corrupt stream or "
                           "cross-arch sender)");
    }
    if (h.arena_bytes != arena_bytes) {
      throw dmlctpu::Error("staged wire batch: arena length mismatch");
    }
    if (h.num_rows > h.batch_size) {
      throw dmlctpu::Error("staged wire batch: num_rows > batch_size");
    }
    CheckSpan("label", h.label_off, h.batch_size * sizeof(float), arena_bytes);
    CheckSpan("weight", h.weight_off, h.batch_size * sizeof(float), arena_bytes);
    CheckSpan("row_ptr", h.row_ptr_off, (h.batch_size + 1) * sizeof(int32_t),
              arena_bytes);
    CheckSpan("index", h.index_off, h.nnz_pad * sizeof(int32_t), arena_bytes);
    CheckSpan("value", h.value_off, h.nnz_pad * sizeof(float), arena_bytes);
    CheckSpan("field", h.field_off, h.nnz_pad * sizeof(int32_t), arena_bytes);
    CheckSpan("qid", h.qid_off, h.batch_size * sizeof(int32_t), arena_bytes);
    if (h.label_off == kNoColumn || h.weight_off == kNoColumn ||
        h.row_ptr_off == kNoColumn || h.index_off == kNoColumn ||
        h.value_off == kNoColumn) {
      throw dmlctpu::Error("staged wire batch: required column absent");
    }
    out->num_rows = static_cast<uint32_t>(h.num_rows);
    out->batch_size = h.batch_size;
    out->nnz_pad = h.nnz_pad;
    out->max_index = h.max_index;
    out->batch = nullptr;  // receiver owns the arena; Free(NULL) is a no-op
    out->arena = arena;
    out->arena_bytes = arena_bytes;
    out->label_off = h.label_off;
    out->weight_off = h.weight_off;
    out->row_ptr_off = h.row_ptr_off;
    out->index_off = h.index_off;
    out->value_off = h.value_off;
    out->field_off = h.field_off;
    out->qid_off = h.qid_off;
    out->lineage = h.lineage;
    return 0;
  });
}

int DmlcTpuStagedBatcherBeforeFirst(DmlcTpuStagedBatcherHandle handle) {
  return Guard([&] {
    auto* ctx = static_cast<BatcherCtx*>(handle);
    ctx->borrowed.Reset();
    ctx->batcher->BeforeFirst();
    return 0;
  });
}

int64_t DmlcTpuStagedBatcherBytesRead(DmlcTpuStagedBatcherHandle handle) {
  return static_cast<int64_t>(static_cast<BatcherCtx*>(handle)->batcher->BytesRead());
}

int DmlcTpuStagedBatcherSetPoolKnobs(DmlcTpuStagedBatcherHandle handle,
                                     int num_workers, uint64_t buffer_bytes,
                                     uint64_t chunk_bytes, int* out_applied) {
  return Guard([&] {
    auto* ctx = static_cast<BatcherCtx*>(handle);
    *out_applied = SetPoolKnobsOn<uint32_t>(
        ctx->batcher->parser(), num_workers, buffer_bytes, chunk_bytes);
    return 0;
  });
}

int DmlcTpuStagedBatcherGetPoolKnobs(DmlcTpuStagedBatcherHandle handle,
                                     int* num_workers, uint64_t* buffer_bytes,
                                     uint64_t* chunk_bytes, int* out_applied) {
  return Guard([&] {
    auto* ctx = static_cast<BatcherCtx*>(handle);
    *out_applied = GetPoolKnobsOn<uint32_t>(
        ctx->batcher->parser(), num_workers, buffer_bytes, chunk_bytes);
    return 0;
  });
}

void DmlcTpuStagedBatcherFree(DmlcTpuStagedBatcherHandle handle) {
  delete static_cast<BatcherCtx*>(handle);
}

int DmlcTpuRecordBatcherCreate(const char* uri, unsigned part, unsigned num_parts,
                               uint64_t records_cap, uint64_t bytes_cap,
                               DmlcTpuRecordBatcherHandle* out) {
  return DmlcTpuRecordBatcherCreateEx(uri, part, num_parts, records_cap,
                                      bytes_cap, 0, out);
}

int DmlcTpuRecordBatcherCreateEx(const char* uri, unsigned part,
                                 unsigned num_parts, uint64_t records_cap,
                                 uint64_t bytes_cap, int recover,
                                 DmlcTpuRecordBatcherHandle* out) {
  return Guard([&] {
    auto ctx = std::make_unique<RecordBatcherCtx>();
    auto split = dmlctpu::InputSplit::Create(uri, part, num_parts, "recordio");
    ctx->batcher = std::make_unique<dmlctpu::data::RecordBatcher>(
        std::move(split), records_cap, bytes_cap, recover != 0);
    // report the same clamped caps RecordBatcher sizes its buffers with —
    // records_cap=0 would otherwise make consumers mis-shape the offsets view
    ctx->records_cap = std::max<uint64_t>(records_cap, 1);
    ctx->bytes_cap = std::max<uint64_t>(bytes_cap, 1);
    *out = ctx.release();
    return 0;
  });
}

int DmlcTpuRecordBatcherNext(DmlcTpuRecordBatcherHandle handle,
                             DmlcTpuRecordBatchC* out) {
  return Guard([&] {
    auto* ctx = static_cast<RecordBatcherCtx*>(handle);
    if (ctx->borrowed != nullptr) {
      ctx->batcher->Recycle(&ctx->borrowed);
    }
    if (!ctx->batcher->Next(&ctx->borrowed)) return 0;
    const auto* b = ctx->borrowed;
    out->num_records = b->num_records;
    out->records_cap = ctx->records_cap;
    out->bytes_cap = ctx->bytes_cap;
    out->bytes_used = b->bytes_used;
    out->bytes = b->bytes.data();
    out->offsets = b->offsets.data();
    return 1;
  });
}

int DmlcTpuRecordBatcherBeforeFirst(DmlcTpuRecordBatcherHandle handle) {
  return Guard([&] {
    auto* ctx = static_cast<RecordBatcherCtx*>(handle);
    ctx->batcher->BeforeFirst();
    if (ctx->borrowed != nullptr) {
      ctx->batcher->Recycle(&ctx->borrowed);
    }
    return 0;
  });
}

int64_t DmlcTpuRecordBatcherBytesRead(DmlcTpuRecordBatcherHandle handle) {
  return static_cast<int64_t>(
      static_cast<RecordBatcherCtx*>(handle)->batcher->BytesRead());
}

void DmlcTpuRecordBatcherFree(DmlcTpuRecordBatcherHandle handle) {
  delete static_cast<RecordBatcherCtx*>(handle);
}


}  // extern "C"
