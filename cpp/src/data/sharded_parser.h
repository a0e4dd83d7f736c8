// sharded_parser.h — parallel sharded parse pool behind the Parser interface.
//
// The staging bottleneck (BENCH_r05: staging_to_hbm 283 MB/s vs 969 MB/s
// RecordIO) is the single parser stream: parse, pack, and device_put
// serialize.  This parser fans the PARSE out over N worker threads, each
// driving an independent inner parser over a small InputSplit part, and
// re-emits the parsed blocks in deterministic part order — so the row
// stream (and therefore every StagedBatcher batch packed from it) is
// IDENTICAL for any worker count, while parse throughput scales with N.
//
// Layout: the user's (part, num_parts) shard is subdivided into V "virtual"
// parts — global split (part*V + j) of (num_parts*V).  V depends only on
// the dataset size and num_parts (NOT on num_workers): byte-range healing
// assigns every record to exactly one virtual part in order, so the
// concatenation over j is the user shard's row stream regardless of V or
// thread count, and ranks that pick different num_workers still cover the
// global dataset exactly once as long as they agree on num_parts.
//
// Workers claim virtual parts from a shared cursor (dynamic load balance),
// parse them chunk-by-chunk into owned block containers, and publish each
// chunk's blocks into a per-part bounded reorder buffer.  The consumer
// drains parts strictly in index order (reorder=true, default) or in
// arrival order (reorder=false, slightly better pipelining, order not
// reproducible across runs).  Total buffered bytes are capped: a producer
// blocks when the buffer is full UNLESS it owns the part the consumer is
// draining (that part must always make progress — this is what keeps the
// pool scaling instead of serializing behind the in-order drain).
//
// Graceful degradation (doc/robustness.md): a part whose parse fails is
// rolled back (its unconsumed queued chunks discarded) and re-parsed from
// the top up to DMLCTPU_SHARD_RETRIES extra attempts (default 2).  Chunk
// boundaries are a pure function of the part's bytes (ReadChunk fills its
// buffer fully), so the re-parse reproduces the identical chunk sequence;
// chunks the consumer already popped are replayed silently and publishing
// resumes at the first unconsumed chunk — the emitted row stream stays
// bit-identical to a fault-free epoch no matter where the failure landed.
// Each round trip counts shard.part_retries; fault point
// "shard.worker.chunk" injects a transient chunk-parse failure here.
//
// Live retuning (doc/autotune.md): SetPoolKnobs retargets the worker count
// and the buffer cap while the pool runs.  Growing spawns threads
// immediately (they join the claim cursor); shrinking is lazy — surplus
// workers retire at their next part boundary, so an in-flight part always
// finishes and the emitted stream stays bit-identical (the stream is a pure
// function of the part order, never of which thread parsed a part).
#ifndef DMLCTPU_SRC_DATA_SHARDED_PARSER_H_
#define DMLCTPU_SRC_DATA_SHARDED_PARSER_H_

#include <algorithm>
#include <atomic>
#include <condition_variable>
#include <cstdlib>
#include <deque>
#include <exception>
#include <map>
#include <memory>
#include <mutex>
#include <string>
#include <thread>
#include <utility>
#include <vector>

#include "./parser_impl.h"
#include "../io/line_split.h"
#include "dmlctpu/data.h"
#include "dmlctpu/fault.h"
#include "dmlctpu/io/filesystem.h"
#include "dmlctpu/logging.h"
#include "dmlctpu/retry.h"
#include "dmlctpu/row_block.h"
#include "dmlctpu/telemetry.h"

namespace dmlctpu {
namespace data {

template <typename IndexType, typename DType = real_t>
class ShardedParser : public Parser<IndexType, DType> {
 public:
  using Blocks = std::vector<RowBlockContainer<IndexType, DType>>;

  /*! \brief target virtual-part size; V is derived from it so partitioning
   *  is a pure function of (dataset bytes, num_parts) — never of
   *  num_workers */
  static constexpr size_t kTargetPartBytes = 8u << 20u;
  static constexpr unsigned kMinVirtualParts = 8;
  static constexpr unsigned kMaxVirtualParts = 1024;
  /*! \brief default cap on buffered parsed bytes across all parts */
  static constexpr size_t kDefaultBufferBytes = 64u << 20u;

  ShardedParser(const std::string& uri, unsigned part, unsigned num_parts,
                const std::string& format, int num_workers,
                bool reorder = true, size_t buffer_bytes = kDefaultBufferBytes)
      : uri_(uri),
        format_(format),
        part_(part),
        num_parts_(num_parts),
        reorder_(reorder),
        worker_target_(std::max(num_workers, 1)),
        buffer_bytes_(std::max<size_t>(buffer_bytes, 1u << 20u)) {
    TCHECK_LT(part, num_parts) << "part index must be < num_parts";
    io::URISpec spec(uri, part, num_parts);
    TCHECK(spec.uri != "stdin" && spec.uri != "-")
        << "sharded parsing needs a seekable byte-range source, not stdin";
    virtual_parts_ = PickVirtualParts(spec.uri, num_parts);
    // the pool starts on BeforeFirst (or lazily on the first Next): starting
    // here would let the canonical create-then-BeforeFirst sequence discard
    // in-flight parse work whose bytes already hit bytes_read_
  }

  ~ShardedParser() override { Stop(); }

  void BeforeFirst() override {
    Stop();
    {
      std::lock_guard<std::mutex> lk(mu_);
      parts_.clear();
      next_claim_ = 0;
      emit_part_ = 0;
      buffered_bytes_ = 0;
      error_ = nullptr;
      stop_ = false;
      telemetry::stage::ShardNextPart().Set(0);
      telemetry::stage::ShardEmitPart().Set(0);
    }
    {
      std::lock_guard<std::mutex> lk(mu_);
      RecycleCurBlocks();
    }
    blk_ptr_ = 0;
    cur_lineage_ = -1;
    Start();
  }

  bool Next() override {
    // atomic flag, not workers_.empty(): SetPoolKnobs may be growing the
    // vector from another thread while the consumer sits in Next()
    if (!pool_started_.load(std::memory_order_acquire)) {
      Start();  // direct use without a BeforeFirst
    }
    while (true) {
      while (blk_ptr_ < cur_blocks_.size()) {
        if (cur_blocks_[blk_ptr_].Size() == 0) {
          ++blk_ptr_;
          continue;
        }
        block_ = cur_blocks_[blk_ptr_].GetBlock();
        ++blk_ptr_;
        return true;
      }
      if (!PopNext()) return false;
    }
  }

  const RowBlock<IndexType, DType>& Value() const override { return block_; }
  size_t BytesRead() const override {
    return bytes_read_.load(std::memory_order_relaxed);
  }
  /*! \brief lineage of the chunk behind the current Value(): consumer-thread
   *  state like blk_ptr_/cur_blocks_ (set in TakeFront on the Next() thread,
   *  read on the same thread) */
  int64_t LineageId() const override { return cur_lineage_; }

  unsigned virtual_parts() const { return virtual_parts_; }

  /*! \brief retune the pool live.  num_workers <= 0 / buffer_bytes == 0 /
   *  chunk_bytes == 0 leave the respective knob unchanged; workers and the
   *  buffer clamp to their floors (1 worker, 1 MiB).  chunk_bytes raises
   *  the chunk-read size of inner parsers created from here on (parts
   *  already parsing finish at their current size; HintChunkSize is
   *  grow-only).  Safe against a concurrently-draining consumer: growth
   *  spawns into the running pool, shrink retires workers lazily at part
   *  boundaries, and a bigger buffer cap wakes blocked producers. */
  void SetPoolKnobs(int num_workers, size_t buffer_bytes,
                    size_t chunk_bytes = 0) {
    std::lock_guard<std::mutex> plk(pool_mu_);
    int spawn = 0;
    {
      std::lock_guard<std::mutex> lk(mu_);
      if (buffer_bytes != 0) {
        buffer_bytes_ = std::max<size_t>(buffer_bytes, 1u << 20u);
      }
      if (chunk_bytes != 0) chunk_bytes_ = chunk_bytes;
      if (num_workers > 0) {
        worker_target_ = std::max(num_workers, 1);
        // grow a live pool now; with no live workers (between epochs) the
        // next Start() simply spawns the new target
        if (!workers_.empty() && !stop_ && !error_ &&
            live_workers_ < worker_target_ &&
            next_claim_ < virtual_parts_) {
          spawn = worker_target_ - live_workers_;
          live_workers_ = worker_target_;
        }
      }
      telemetry::stage::ShardPoolWorkers().Set(worker_target_);
      telemetry::stage::ShardPoolBufferBytes().Set(
          static_cast<int64_t>(buffer_bytes_));
    }
    for (int i = 0; i < spawn; ++i) {
      workers_.emplace_back([this] { WorkerLoop(); });
    }
    // a raised buffer cap (or a retargeted pool) may unblock either side
    cv_produce_.notify_all();
    cv_consume_.notify_all();
  }

  int pool_workers() {
    std::lock_guard<std::mutex> lk(mu_);
    return worker_target_;
  }
  size_t pool_buffer_bytes() {
    std::lock_guard<std::mutex> lk(mu_);
    return buffer_bytes_;
  }
  size_t pool_chunk_bytes() {
    std::lock_guard<std::mutex> lk(mu_);
    return chunk_bytes_;
  }

 private:
  struct QueuedChunk {
    Blocks blocks;
    size_t cost = 0;      // byte cost against the buffer cap
    int64_t lineage = -1;  // (global virtual part << 32) | chunk index
  };

  struct PartQueue {
    std::deque<QueuedChunk> q;
    bool done = false;
    size_t popped = 0;  // chunks the consumer took (a re-parse skips these)
  };

  /*! \brief failed-part re-parse budget: DMLCTPU_SHARD_RETRIES extra
   *  attempts on top of the first (default 2; 0 disables) */
  static int ShardMaxAttempts() {
    static int attempts = [] {
      const char* v = std::getenv("DMLCTPU_SHARD_RETRIES");
      int retries = (v != nullptr && v[0] != '\0') ? std::atoi(v) : 2;
      return std::max(retries, 0) + 1;
    }();
    return attempts;
  }

  static unsigned PickVirtualParts(const std::string& path,
                                   unsigned num_parts) {
    io::URI u(path);
    io::FileSystem* fs = io::FileSystem::GetInstance(u);
    io::LineSplitter probe(fs, path.c_str(), 0, 1);
    size_t total = probe.GetTotalSize();
    size_t per_part = total / std::max(num_parts, 1u);
    size_t v = (per_part + kTargetPartBytes - 1) / kTargetPartBytes;
    return static_cast<unsigned>(std::min<size_t>(
        std::max<size_t>(v, kMinVirtualParts), kMaxVirtualParts));
  }

  /*! \brief uri with extra ?args spliced in before the #fragment */
  static std::string InjectArgs(const std::string& uri,
                                const std::string& extra) {
    size_t hash = uri.find('#');
    std::string head =
        hash == std::string::npos ? uri : uri.substr(0, hash);
    std::string frag = hash == std::string::npos ? "" : uri.substr(hash);
    head += (head.find('?') == std::string::npos ? "?" : "&") + extra;
    return head + frag;
  }

  void Start() {
    std::lock_guard<std::mutex> plk(pool_mu_);
    int target;
    {
      std::lock_guard<std::mutex> lk(mu_);
      target = worker_target_;
      live_workers_ = target;
      telemetry::stage::ShardPoolWorkers().Set(target);
      telemetry::stage::ShardPoolBufferBytes().Set(
          static_cast<int64_t>(buffer_bytes_));
    }
    for (int i = 0; i < target; ++i) {
      workers_.emplace_back([this] { WorkerLoop(); });
    }
    pool_started_.store(true, std::memory_order_release);
  }

  void Stop() {
    {
      std::lock_guard<std::mutex> lk(mu_);
      stop_ = true;
    }
    cv_produce_.notify_all();
    cv_consume_.notify_all();
    // pool_mu_ serializes against SetPoolKnobs growing workers_ mid-join
    std::lock_guard<std::mutex> plk(pool_mu_);
    for (auto& t : workers_) {
      if (t.joinable()) t.join();
    }
    workers_.clear();
    pool_started_.store(false, std::memory_order_release);
  }

  void WorkerLoop() {
    try {
      for (;;) {
        unsigned j;
        {
          std::lock_guard<std::mutex> lk(mu_);
          // lazy shrink: surplus workers retire between parts, so the part
          // in flight always completes and the stream stays deterministic.
          // The retire decision and the live-count move share ONE lock
          // hold — concurrent retirees can never overshoot the target.
          if (live_workers_ > worker_target_ || stop_ || error_ ||
              next_claim_ >= virtual_parts_) {
            --live_workers_;
            break;
          }
          j = next_claim_++;
          telemetry::stage::ShardNextPart().Set(next_claim_);
          parts_[j];  // publish the (empty) queue so the consumer can see it
        }
        cv_consume_.notify_all();  // consumer may be waiting on parts_[j]
        ParsePartWithRetry(j);
        {
          std::lock_guard<std::mutex> lk(mu_);
          parts_[j].done = true;
        }
        cv_consume_.notify_all();
      }
    } catch (...) {
      {
        std::lock_guard<std::mutex> lk(mu_);
        if (!error_) error_ = std::current_exception();
        --live_workers_;
      }
      cv_produce_.notify_all();
    }
    cv_consume_.notify_all();
  }

  /*! \brief parse part j, re-parsing from the top on failure; chunks the
   *  consumer already popped replay silently (identical bytes re-parse to
   *  identical blocks) and publishing resumes at the first unconsumed
   *  chunk.  Exhaustion rethrows and the pool relays the error. */
  void ParsePartWithRetry(unsigned j) {
    const int max_attempts = ShardMaxAttempts();
    retry::Backoff backoff(retry::IoPolicy());
    size_t skip = 0;
    // pin the chunk-size knob for ALL attempts of this part: a re-parse
    // replays already-popped chunks by count, which is only correct when
    // every attempt reproduces identical chunk boundaries
    size_t chunk_bytes;
    {
      std::lock_guard<std::mutex> lk(mu_);
      chunk_bytes = chunk_bytes_;
    }
    for (int attempt = 1;; ++attempt) {
      try {
        ParseOnePart(j, skip, chunk_bytes);
        return;
      } catch (const Error& e) {
        bool can_retry;
        {
          std::lock_guard<std::mutex> lk(mu_);
          auto it = parts_.find(j);
          can_retry = !stop_ && !error_ && attempt < max_attempts &&
                      it != parts_.end();
          if (can_retry) {
            skip = it->second.popped;
            RollbackPartLocked(&it->second);
          }
        }
        if (!can_retry) throw;
        // discarded chunks free buffer budget other producers may be
        // blocked on
        cv_produce_.notify_all();
        telemetry::stage::ShardPartRetries().Add(1);
        TLOG(Warning) << "shard: re-parsing part " << j << " (attempt "
                      << attempt << "/" << max_attempts << "): " << e.what();
        backoff.SleepNext();
      }
    }
  }

  /*! \brief discard part j's unconsumed queued chunks (caller holds mu_);
   *  buffered-byte accounting is unwound, bytes_read_ is NOT — those bytes
   *  really were read and the re-parse reads them again */
  void RollbackPartLocked(PartQueue* pq) {
    for (auto& ch : pq->q) {
      buffered_bytes_ -= ch.cost;
      if (free_pool_.size() < static_cast<size_t>(2 * worker_target_)) {
        for (auto& b : ch.blocks) b.Clear();
        free_pool_.push_back(std::move(ch.blocks));
      }
    }
    pq->q.clear();
    telemetry::stage::ShardBufferedBytes().Set(
        static_cast<int64_t>(buffered_bytes_));
  }

  /*! \brief lineage id: which source bytes produced a chunk — a pure
   *  function of the partition, so identical across re-parses and
   *  completely independent of whether tracing is armed */
  int64_t ChunkLineage(unsigned j, size_t chunk) const {
    return static_cast<int64_t>(
        (static_cast<uint64_t>(part_ * virtual_parts_ + j) << 32) |
        (static_cast<uint64_t>(chunk) & 0xffffffffu));
  }

  void ParseOnePart(unsigned j, size_t skip_chunks = 0,
                    size_t chunk_bytes = 0) {
    telemetry::ScopedSpan span("shard.part");
    span.set_lineage(ChunkLineage(j, 0));  // the part, by its first chunk
    telemetry::ScopedAccum part_timer(telemetry::stage::ShardPartUs());
    telemetry::stage::ShardParts().Add(1);
    // nthread=1: worker threads ARE the parse parallelism; parseahead=0
    // skips the inner parse-ahead thread so CallParseNext hands back owned
    // containers with zero copies.  chunkbytes (live knob, pinned per part
    // by the caller) raises the inner split's chunk-read size — each part
    // picks up the value current at its parse start, so a mid-epoch retune
    // cannot perturb the emitted stream (rows are chunk-independent).
    std::string extra = "nthread=1&parseahead=0";
    if (chunk_bytes != 0) {
      extra += "&chunkbytes=" + std::to_string(chunk_bytes);
    }
    std::string inner_uri = InjectArgs(uri_, extra);
    auto parser = Parser<IndexType, DType>::Create(
        inner_uri.c_str(), part_ * virtual_parts_ + j,
        num_parts_ * virtual_parts_, format_.c_str());
    auto* impl = dynamic_cast<ParserImpl<IndexType, DType>*>(parser.get());
    size_t last_bytes = 0;
    size_t chunk_idx = 0;
    for (;;) {
      Blocks blocks;
      if (impl != nullptr) {
        // recycle consumed containers: their heap storage (vector capacity)
        // survives the round trip, so steady-state parsing allocates nothing
        {
          std::lock_guard<std::mutex> lk(mu_);
          if (!free_pool_.empty()) {
            blocks = std::move(free_pool_.back());
            free_pool_.pop_back();
          }
        }
        // the inner parser's parse.chunk / parse.block spans are this
        // chunk's: they take its lineage from the thread, not from a slot
        // that the other workers write
        telemetry::ScopedLineage of_chunk(ChunkLineage(j, chunk_idx));
        if (!impl->CallParseNext(&blocks)) break;
      } else {
        // fallback for parser types that hide their impl: copy block views
        telemetry::ScopedLineage of_chunk(ChunkLineage(j, chunk_idx));
        if (!parser->Next()) break;
        blocks.emplace_back();
        blocks.back().Push(parser->Value());
      }
      size_t nb = parser->BytesRead();
      size_t delta = nb - last_bytes;
      last_bytes = nb;
      const size_t this_chunk = chunk_idx++;
      if (this_chunk < skip_chunks) {
        // re-parse replaying chunks the consumer already took from a prior
        // attempt: identical bytes re-parsed to identical blocks, so drop
        // them (the bytes were really read again and stay counted)
        std::lock_guard<std::mutex> lk(mu_);
        if (stop_ || error_) return;
        telemetry::stage::ShardBytes().Add(delta);
        bytes_read_.fetch_add(delta, std::memory_order_relaxed);
        if (free_pool_.size() < static_cast<size_t>(2 * worker_target_)) {
          for (auto& b : blocks) b.Clear();
          free_pool_.push_back(std::move(blocks));
        }
        continue;
      }
      DMLCTPU_FAULT_POINT(fp_chunk, "shard.worker.chunk");
      if (fp_chunk.Fire() != fault::Mode::kNone) {
        // before publish: this attempt's already-published chunks roll back
        // in ParsePartWithRetry, so the re-parse re-emits the same stream
        throw retry::TransientError(
            "shard worker: injected chunk-parse failure in part " +
            std::to_string(j));
      }
      size_t cost = 0;
      for (const auto& b : blocks) cost += b.MemCostBytes();
      {
        std::unique_lock<std::mutex> lk(mu_);
        {
          // producer stall: blocked because the reorder buffer is full —
          // the downstream (pack/H2D) is the slow side
          telemetry::ScopedAccum wait(
              telemetry::stage::ShardProducerWaitUs());
          cv_produce_.wait(lk, [&] {
            return stop_ || error_ || buffered_bytes_ < buffer_bytes_ ||
                   (reorder_ && j == emit_part_);
          });
        }
        if (stop_ || error_) return;
        parts_[j].q.push_back(QueuedChunk{std::move(blocks), cost,
                                          ChunkLineage(j, this_chunk)});
        buffered_bytes_ += cost;
        telemetry::stage::ShardBufferedBytes().Set(
            static_cast<int64_t>(buffered_bytes_));
        telemetry::stage::ShardChunks().Add(1);
        telemetry::stage::ShardBytes().Add(delta);
        // count bytes only once their blocks are published, so work that a
        // Stop/BeforeFirst discards never lands in BytesRead (bench derives
        // throughput from its deltas)
        bytes_read_.fetch_add(delta, std::memory_order_relaxed);
      }
      cv_consume_.notify_all();
    }
    // tail bytes past the last published chunk (EOF detection): still real
    // reads of this part, but drop them if the epoch is being torn down
    std::lock_guard<std::mutex> lk(mu_);
    if (stop_ || error_) return;
    telemetry::stage::ShardBytes().Add(parser->BytesRead() - last_bytes);
    bytes_read_.fetch_add(parser->BytesRead() - last_bytes,
                          std::memory_order_relaxed);
  }

  /*! \brief pull the next Blocks into cur_blocks_; false at end of epoch */
  /*! \brief true when PopNext's scan loop can make progress (caller holds
   *  mu_): an error to rethrow, a block/done-part to act on, or the epoch is
   *  over.  Mirrors the branch structure of PopNext exactly — keep in sync. */
  bool ConsumerWakeLocked() const {
    if (error_) return true;
    if (reorder_) {
      auto it = parts_.find(emit_part_);
      if (it == parts_.end()) return emit_part_ >= virtual_parts_;
      return !it->second.q.empty() || it->second.done;
    }
    for (const auto& kv : parts_) {
      if (!kv.second.q.empty() || kv.second.done) return true;
    }
    return next_claim_ >= virtual_parts_ && parts_.empty();
  }

  bool PopNext() {
    std::unique_lock<std::mutex> lk(mu_);
    for (;;) {
      if (error_) {
        auto err = error_;
        stop_ = true;
        lk.unlock();
        cv_produce_.notify_all();
        std::rethrow_exception(err);
      }
      if (reorder_) {
        auto it = parts_.find(emit_part_);
        if (it != parts_.end()) {
          if (!it->second.q.empty()) {
            TakeFront(&it->second);
            return true;
          }
          if (it->second.done) {
            parts_.erase(it);
            ++emit_part_;
            telemetry::stage::ShardEmitPart().Set(emit_part_);
            // a producer blocked on the full buffer may have just become
            // the emit part (its wait exemption turned true): wake it, or
            // the pipeline wedges with everyone asleep
            cv_produce_.notify_all();
            continue;
          }
        } else if (emit_part_ >= virtual_parts_) {
          return false;
        }
      } else {
        auto it = std::find_if(parts_.begin(), parts_.end(), [](auto& kv) {
          return !kv.second.q.empty();
        });
        if (it != parts_.end()) {
          TakeFront(&it->second);
          bool drained = it->second.done && it->second.q.empty();
          if (drained) parts_.erase(it);
          return true;
        }
        // drop finished empty parts, then check for end of epoch
        for (auto pit = parts_.begin(); pit != parts_.end();) {
          pit = pit->second.done ? parts_.erase(pit) : std::next(pit);
        }
        if (next_claim_ >= virtual_parts_ && parts_.empty()) return false;
      }
      {
        // consumer stall: nothing parsed and buffered for the emit part —
        // the parse side is the slow side
        telemetry::ScopedAccum wait(telemetry::stage::ShardConsumerWaitUs());
        cv_consume_.wait(lk, [&] { return ConsumerWakeLocked(); });
      }
    }
  }

  void TakeFront(PartQueue* pq) {
    RecycleCurBlocks();
    ++pq->popped;  // a re-parse must replay (not republish) this chunk
    cur_blocks_ = std::move(pq->q.front().blocks);
    cur_lineage_ = pq->q.front().lineage;
    buffered_bytes_ -= pq->q.front().cost;
    telemetry::stage::ShardBufferedBytes().Set(
        static_cast<int64_t>(buffered_bytes_));
    pq->q.pop_front();
    blk_ptr_ = 0;
    cv_produce_.notify_all();
  }

  /*! \brief hand the drained cur_blocks_ storage back to the producers
   *  (caller holds mu_); Clear() keeps each container's capacity */
  void RecycleCurBlocks() {
    if (cur_blocks_.empty()) return;
    if (free_pool_.size() < static_cast<size_t>(2 * worker_target_)) {
      for (auto& b : cur_blocks_) b.Clear();
      free_pool_.push_back(std::move(cur_blocks_));
    }
    cur_blocks_.clear();
  }

  const std::string uri_;
  const std::string format_;
  const unsigned part_;
  const unsigned num_parts_;
  const bool reorder_;
  // live-retunable knobs (SetPoolKnobs), guarded by mu_
  int worker_target_;
  size_t buffer_bytes_;
  size_t chunk_bytes_ = 0;  // 0 = the split's own default
  int live_workers_ = 0;
  unsigned virtual_parts_ = 0;

  // serializes workers_ mutation (Start / Stop / SetPoolKnobs growth);
  // always taken before mu_, never while holding it
  std::mutex pool_mu_;
  std::mutex mu_;
  std::condition_variable cv_produce_;
  std::condition_variable cv_consume_;
  std::map<unsigned, PartQueue> parts_;
  unsigned next_claim_ = 0;
  unsigned emit_part_ = 0;
  size_t buffered_bytes_ = 0;
  bool stop_ = false;
  std::exception_ptr error_;
  std::atomic<bool> pool_started_{false};
  std::vector<std::thread> workers_;
  std::vector<Blocks> free_pool_;  // consumed containers awaiting reuse (mu_)
  std::atomic<size_t> bytes_read_{0};

  Blocks cur_blocks_;
  size_t blk_ptr_ = 0;
  int64_t cur_lineage_ = -1;  // consumer-thread state (see LineageId)
  RowBlock<IndexType, DType> block_;
};

}  // namespace data
}  // namespace dmlctpu
#endif  // DMLCTPU_SRC_DATA_SHARDED_PARSER_H_
