// dmlctpu/c_api.h — flat C surface of the native runtime, consumed by the
// Python package through ctypes (no pybind11 in this build).  All functions
// return 0 on success / -1 on error (query DmlcTpuGetLastError), except
// "next" style calls which return 1 = item, 0 = end, -1 = error.
// Handles are opaque; every *Free is idempotent on NULL.
#ifndef DMLCTPU_C_API_H_
#define DMLCTPU_C_API_H_

#include <stddef.h>
#include <stdint.h>

#ifdef __cplusplus
extern "C" {
#endif

/*! \brief borrowed view of a parsed CSR batch (uint64 indices, f32 values) */
typedef struct {
  uint64_t size;            /* rows */
  const uint64_t* offset;   /* length size+1, starts at 0 */
  const float* label;       /* length size */
  const float* weight;      /* length size, or NULL */
  const uint64_t* qid;      /* length size, or NULL */
  const uint64_t* field;    /* length offset[size], or NULL */
  const uint64_t* index;    /* length offset[size] */
  const float* value;       /* length offset[size], or NULL (implicit 1.0) */
} DmlcTpuRowBlockC;

/*! \brief last error message on this thread (empty string if none) */
const char* DmlcTpuGetLastError(void);

/* ---- Parser: uri → stream of RowBlocks ---------------------------------- */
typedef void* DmlcTpuParserHandle;
int DmlcTpuParserCreate(const char* uri, unsigned part, unsigned num_parts,
                        const char* format, DmlcTpuParserHandle* out);
/*! \brief parser with a parallel sharded parse pool.  num_workers 0..1 is
 *  exactly DmlcTpuParserCreate (bit-identical stream); num_workers > 1 fans
 *  the parse over worker threads driving per-virtual-part inner parsers;
 *  num_workers < 0 forces the pool with |num_workers| workers even when
 *  that is 1 — same stream, but live-retunable via *SetPoolKnobs (how the
 *  autotuner arms an iterator that starts at one worker).
 *  reorder != 0 (recommended) re-emits blocks in deterministic part order,
 *  so the row stream is IDENTICAL for any worker count; reorder == 0 emits
 *  in arrival order.  buffer_bytes caps buffered parsed bytes (0 = default
 *  64 MiB).  Needs a seekable byte-range source (not stdin). */
int DmlcTpuParserCreateEx(const char* uri, unsigned part, unsigned num_parts,
                          const char* format, int num_workers, int reorder,
                          uint64_t buffer_bytes, DmlcTpuParserHandle* out);
/*! \brief pin the default parse-thread pool size for parsers created WITHOUT
 *  an explicit ?nthread= URI arg (an explicit value always wins).  0 restores
 *  the per-parser heuristic max(cores/2 - 4, 1).  Takes effect for parsers
 *  created after the call. */
int DmlcTpuSetDefaultParseThreads(int nthread);
int DmlcTpuGetDefaultParseThreads(int* out);
/*! \brief retune a live sharded parse pool (parsers created with
 *  num_workers > 1): num_workers <= 0 / buffer_bytes == 0 / chunk_bytes
 *  == 0 each leave that knob unchanged; workers and the buffer clamp to
 *  their floors (1 worker, 1 MiB).  Growth spawns workers immediately;
 *  shrink retires surplus workers at their next part boundary; chunk_bytes
 *  raises the chunk-read size of parts parsed from here on — through all
 *  of it the emitted row stream stays bit-identical.  *out_applied = 1
 *  when the parser has a pool, 0 for single-stream parsers (no-op). */
int DmlcTpuParserSetPoolKnobs(DmlcTpuParserHandle handle, int num_workers,
                              uint64_t buffer_bytes, uint64_t chunk_bytes,
                              int* out_applied);
int DmlcTpuParserNext(DmlcTpuParserHandle handle, DmlcTpuRowBlockC* out);
int DmlcTpuParserBeforeFirst(DmlcTpuParserHandle handle);
int64_t DmlcTpuParserBytesRead(DmlcTpuParserHandle handle);
void DmlcTpuParserFree(DmlcTpuParserHandle handle);

/* ---- InputSplit: sharded raw records ------------------------------------ */
typedef void* DmlcTpuInputSplitHandle;
int DmlcTpuInputSplitCreate(const char* uri, const char* index_uri, unsigned part,
                            unsigned num_parts, const char* type, int shuffle, int seed,
                            uint64_t batch_size, DmlcTpuInputSplitHandle* out);
/*! \brief next record; *data/*size borrowed until the next call */
int DmlcTpuInputSplitNextRecord(DmlcTpuInputSplitHandle handle, const void** data,
                                uint64_t* size);
int DmlcTpuInputSplitNextChunk(DmlcTpuInputSplitHandle handle, const void** data,
                               uint64_t* size);
int DmlcTpuInputSplitBeforeFirst(DmlcTpuInputSplitHandle handle);
int DmlcTpuInputSplitResetPartition(DmlcTpuInputSplitHandle handle, unsigned part,
                                    unsigned num_parts);
int64_t DmlcTpuInputSplitTotalSize(DmlcTpuInputSplitHandle handle);
void DmlcTpuInputSplitFree(DmlcTpuInputSplitHandle handle);

/* ---- RecordIO container ------------------------------------------------- */
typedef void* DmlcTpuRecordIOWriterHandle;
typedef void* DmlcTpuRecordIOReaderHandle;
int DmlcTpuRecordIOWriterCreate(const char* uri, DmlcTpuRecordIOWriterHandle* out);
int DmlcTpuRecordIOWriterWrite(DmlcTpuRecordIOWriterHandle handle, const void* data,
                               uint64_t size);
/*! \brief flush + finalize the underlying stream, surfacing upload errors
 *         (-1 + DmlcTpuGetLastError).  Remote backends (s3/azure/hdfs)
 *         finalize lazily; Free alone LOGS AND DISCARDS a failed final
 *         flush, so callers who must know the object landed call Close
 *         first.  Idempotent. */
int DmlcTpuRecordIOWriterClose(DmlcTpuRecordIOWriterHandle handle);
/*! \brief closes the underlying stream (failures logged, not reported) */
void DmlcTpuRecordIOWriterFree(DmlcTpuRecordIOWriterHandle handle);
int DmlcTpuRecordIOReaderCreate(const char* uri, DmlcTpuRecordIOReaderHandle* out);
/*! \brief as Create; recover != 0 skips corrupt record spans (resyncing to
 *         the next record head and counting record.corrupt_skipped) instead
 *         of failing the read — see doc/robustness.md */
int DmlcTpuRecordIOReaderCreateEx(const char* uri, int recover,
                                  DmlcTpuRecordIOReaderHandle* out);
int DmlcTpuRecordIOReaderNext(DmlcTpuRecordIOReaderHandle handle, const void** data,
                              uint64_t* size);
/*! \brief corrupt spans skipped so far by this reader (recover mode) */
int64_t DmlcTpuRecordIOReaderCorruptSkipped(DmlcTpuRecordIOReaderHandle handle);
void DmlcTpuRecordIOReaderFree(DmlcTpuRecordIOReaderHandle handle);

/* ---- StagedBatcher: parse→pack→pad pipeline for device staging ---------- */
typedef void* DmlcTpuStagedBatcherHandle;

/*! \brief borrowed view of one fixed-shape padded CSR batch.
 *
 *  Row membership is the CSR row pointer (the reference RowBlock's own
 *  offset[size+1] layout): row r's nonzeros span
 *  [row_ptr[r], row_ptr[r+1]); padding rows are empty; slots in
 *  [row_ptr[batch_size], nnz_pad) are value-0 padding lanes. */
typedef struct {
  uint32_t num_rows;        /* true rows (rest is padding) */
  uint64_t batch_size;      /* padded row count */
  uint64_t nnz_pad;         /* padded nonzero count (multiple of nnz_bucket) */
  int64_t max_index;        /* max feature id seen so far (-1 if none) */
  const float* label;       /* [batch_size] */
  const float* weight;      /* [batch_size], 0 on padding rows */
  const int32_t* row_ptr;   /* [batch_size+1] CSR row pointer */
  const int32_t* index;     /* [nnz_pad] */
  const float* value;       /* [nnz_pad], 0 on padding slots */
  const int32_t* field;     /* [nnz_pad] or NULL */
  const int32_t* qid;       /* [batch_size] query ids or NULL */
} DmlcTpuStagedBatchC;

/*! \brief one fixed-shape padded COO batch in a single OWNED allocation.
 *
 *  All arrays live inside `arena` (64-byte-aligned offsets; the native
 *  pipeline packs directly into it, no extra copy).  The caller owns the
 *  batch and must release it with DmlcTpuStagedBatchFree(batch) once every
 *  consumer of the memory is done; the allocation is then recycled into the
 *  batcher's arena pool, so steady state stages into warm pages.  Unlike
 *  the borrowed DmlcTpuStagedBatchC there is no lifetime coupling to the
 *  next Next() call, so the arena can back zero-copy host arrays and
 *  in-flight DMA. */
typedef struct {
  uint32_t num_rows;
  uint64_t batch_size;
  uint64_t nnz_pad;
  int64_t max_index;
  void* batch;           /* opaque owner; release with DmlcTpuStagedBatchFree */
  void* arena;           /* base address of the allocation */
  uint64_t arena_bytes;
  uint64_t label_off;    /* float [batch_size] */
  uint64_t weight_off;   /* float [batch_size] */
  uint64_t row_ptr_off;  /* int32 [batch_size+1] CSR row pointer */
  uint64_t index_off;    /* int32 [nnz_pad] */
  uint64_t value_off;    /* float [nnz_pad] */
  uint64_t field_off;    /* int32 [nnz_pad]; UINT64_MAX when absent */
  uint64_t qid_off;      /* int32 [batch_size]; UINT64_MAX when absent */
  int64_t lineage;       /* (source virtual part << 32) | chunk index of the
                            batch's first row; -1 when the parser does not
                            track provenance (single-stream paths) */
} DmlcTpuStagedBatchOwnedC;

/*! \brief nnz_max: 0 = unbounded (nnz padded to nnz_bucket multiples); else
 *  a hard per-batch nonzero cap — rows that would exceed it spill into the
 *  next batch and every batch has nnz_pad == nnz_max (fully fixed shapes,
 *  required for multi-host global-array staging) */
/* with_qid stages the per-row query ids (libsvm qid: tokens) alongside
 * label/weight - the ranking-objective column */
int DmlcTpuStagedBatcherCreate(const char* uri, unsigned part, unsigned num_parts,
                               const char* format, uint64_t batch_size,
                               uint64_t nnz_bucket, uint64_t nnz_max,
                               int with_field, int with_qid,
                               DmlcTpuStagedBatcherHandle* out);
/*! \brief staged batcher over a parallel sharded parse pool.  Batch packing
 *  is a pure function of the row stream, so with reorder != 0 every staged
 *  batch is bit-identical to the single-stream batcher for ANY num_workers
 *  — only parse throughput changes.  num_workers 0..1 falls back to the
 *  plain single-stream path; num_workers < 0 forces a |num_workers|-worker
 *  pool (live-retunable even from 1 worker — see DmlcTpuParserCreateEx);
 *  buffer_bytes 0 = default (64 MiB). */
int DmlcTpuStagedBatcherCreateEx(const char* uri, unsigned part,
                                 unsigned num_parts, const char* format,
                                 uint64_t batch_size, uint64_t nnz_bucket,
                                 uint64_t nnz_max, int with_field, int with_qid,
                                 int num_workers, int reorder,
                                 uint64_t buffer_bytes,
                                 DmlcTpuStagedBatcherHandle* out);
/*! \brief next batch (1/0/-1); buffers stay valid until the following call
 *  to Next/BeforeFirst/Free on this handle */
int DmlcTpuStagedBatcherNext(DmlcTpuStagedBatcherHandle handle, DmlcTpuStagedBatchC* out);
/*! \brief take ownership of the next packed batch (1/0/-1); no copy — the
 *  pack thread produced straight into out->arena, and the internal slot is
 *  recycled before return, keeping the parse pipeline moving */
int DmlcTpuStagedBatcherNextOwned(DmlcTpuStagedBatcherHandle handle,
                                  DmlcTpuStagedBatchOwnedC* out);
/*! \brief release an owned batch: its arena returns to the batcher's pool
 *  (or is freed if the pool is full/gone).  NULL is a no-op. */
void DmlcTpuStagedBatchFree(void* batch);
int DmlcTpuStagedBatcherBeforeFirst(DmlcTpuStagedBatcherHandle handle);
int64_t DmlcTpuStagedBatcherBytesRead(DmlcTpuStagedBatcherHandle handle);
/*! \brief retune the batcher's sharded parse pool live — the autotuner's
 *  mid-epoch knob path (semantics as DmlcTpuParserSetPoolKnobs; batches
 *  stay bit-identical because packing is a pure function of the row
 *  stream).  *out_applied = 0 when the batcher wraps a single-stream
 *  parser (created with num_workers <= 1): those can only be retuned by
 *  rebuilding at an epoch boundary. */
int DmlcTpuStagedBatcherSetPoolKnobs(DmlcTpuStagedBatcherHandle handle,
                                     int num_workers, uint64_t buffer_bytes,
                                     uint64_t chunk_bytes, int* out_applied);
/*! \brief read back the pool's current knob values (*out_applied = 0 and
 *  outputs untouched for single-stream parsers) */
int DmlcTpuStagedBatcherGetPoolKnobs(DmlcTpuStagedBatcherHandle handle,
                                     int* num_workers, uint64_t* buffer_bytes,
                                     uint64_t* chunk_bytes, int* out_applied);
void DmlcTpuStagedBatcherFree(DmlcTpuStagedBatcherHandle handle);

/* ---- staged-batch wire codec (dataservice data side channel) ------------- */
/*! \brief serialize an owned batch's geometry into a fixed self-describing
 *  wire header (magic + version + shapes + arena offsets).  The arena bytes
 *  themselves travel separately (they are already one contiguous block —
 *  send batch->arena_bytes bytes from batch->arena verbatim).  `cap` must be
 *  at least DMLCTPU_STAGED_WIRE_HEADER_BYTES; *out_len receives the header
 *  length actually written. */
int DmlcTpuStagedBatchWireHeader(const DmlcTpuStagedBatchOwnedC* batch,
                                 void* buf, uint64_t cap, uint64_t* out_len);
/*! \brief rebind a wire header + received arena into an owned-batch view
 *  without copying: validates magic/version and bounds-checks every column
 *  span against arena_bytes, then fills *out with offsets into the CALLER's
 *  arena.  out->batch is NULL (the caller owns the arena memory; passing
 *  NULL to DmlcTpuStagedBatchFree is a no-op), so the receiver keeps its
 *  recv buffer alive for as long as the arrays are in use. */
int DmlcTpuStagedBatchFromWire(const void* header, uint64_t header_len,
                               void* arena, uint64_t arena_bytes,
                               DmlcTpuStagedBatchOwnedC* out);
#define DMLCTPU_STAGED_WIRE_HEADER_BYTES 112

/* ---- RecordBatcher: RecordIO → packed fixed-shape device batches --------- */
typedef void* DmlcTpuRecordBatcherHandle;

/*! \brief borrowed view of one packed record batch (static shapes for HBM) */
typedef struct {
  uint32_t num_records;     /* true records in this batch */
  uint64_t records_cap;     /* offsets length - 1 (fixed) */
  uint64_t bytes_cap;       /* bytes length (fixed) */
  uint64_t bytes_used;      /* payload bytes before zero padding */
  const char* bytes;        /* [bytes_cap] concatenated payloads */
  const int32_t* offsets;   /* [records_cap+1]; tail repeats bytes_used */
} DmlcTpuRecordBatchC;

int DmlcTpuRecordBatcherCreate(const char* uri, unsigned part, unsigned num_parts,
                               uint64_t records_cap, uint64_t bytes_cap,
                               DmlcTpuRecordBatcherHandle* out);
/*! \brief as Create; recover != 0 skips corrupt record spans inside each
 *         chunk (counted in record.corrupt_skipped) instead of aborting the
 *         epoch — see doc/robustness.md */
int DmlcTpuRecordBatcherCreateEx(const char* uri, unsigned part,
                                 unsigned num_parts, uint64_t records_cap,
                                 uint64_t bytes_cap, int recover,
                                 DmlcTpuRecordBatcherHandle* out);
/*! \brief next batch (1/0/-1); buffers valid until the following call */
int DmlcTpuRecordBatcherNext(DmlcTpuRecordBatcherHandle handle,
                             DmlcTpuRecordBatchC* out);
int DmlcTpuRecordBatcherBeforeFirst(DmlcTpuRecordBatcherHandle handle);
int64_t DmlcTpuRecordBatcherBytesRead(DmlcTpuRecordBatcherHandle handle);
void DmlcTpuRecordBatcherFree(DmlcTpuRecordBatcherHandle handle);

/* ---- generic streams + filesystem metadata (dmlc::Stream::Create /
 *      FileSystem::ListDirectory parity, reference src/io.cc:132-144) ---- */
typedef void* DmlcTpuStreamHandle;
/* mode: "r" / "w" / "a".  Any registered backend URI (file/s3/azure/hdfs/
 * http/https). */
int DmlcTpuStreamCreate(const char* uri, const char* mode,
                        DmlcTpuStreamHandle* out);
/* returns bytes read (0 = EOF) or -1 on error */
int64_t DmlcTpuStreamRead(DmlcTpuStreamHandle handle, void* buf, uint64_t n);
int DmlcTpuStreamWrite(DmlcTpuStreamHandle handle, const void* buf,
                       uint64_t n);
/* flush + close; write errors (e.g. remote upload failure) surface here */
int DmlcTpuStreamClose(DmlcTpuStreamHandle handle);
void DmlcTpuStreamFree(DmlcTpuStreamHandle handle);
/* seekable read stream (SeekStream::CreateForRead); supports Read plus: */
int DmlcTpuSeekStreamCreate(const char* uri, DmlcTpuStreamHandle* out);
int DmlcTpuStreamSeek(DmlcTpuStreamHandle handle, uint64_t pos);
/* returns current position or -1 (only valid for seekable streams) */
int64_t DmlcTpuStreamTell(DmlcTpuStreamHandle handle);
/* newline-separated "type\tsize\tpath" entries (type: f|d; '\\'/'\n'/'\t'
 * inside paths are backslash-escaped); pointer valid until the next call
 * on the same thread.  recursive != 0 descends. */
int DmlcTpuFsListDirectory(const char* uri, int recursive, const char** out);
/* single-path stat into the same format (one line) */
int DmlcTpuFsPathInfo(const char* uri, const char** out);

/* ---- binned epoch cache (cpp/src/data/binned_cache.h) -------------------
 * Quantized columnar cache: opaque per-virtual-part block records behind a
 * self-describing header (meta JSON + part map), RecordIO framed.  The
 * Python layer (dmlc_core_tpu/data/binned_cache.py) packs/unpacks block
 * payloads and owns content-level invalidation; this API owns framing,
 * crash-consistent header patching, per-part seeks, and recover mode. */
typedef void* DmlcTpuBinnedCacheWriterHandle;
typedef void* DmlcTpuBinnedCacheReaderHandle;
int DmlcTpuBinnedCacheWriterCreate(const char* uri, const char* meta_json,
                                   DmlcTpuBinnedCacheWriterHandle* out);
/* append one block for virtual part part_id; rows/nnz are accounting for
 * the part map (readers validate per-part completeness against them) */
int DmlcTpuBinnedCacheWriterWriteBlock(DmlcTpuBinnedCacheWriterHandle handle,
                                       uint32_t part_id, uint64_t rows,
                                       uint64_t nnz, const void* data,
                                       uint64_t size);
/* install finalized quantile cuts (f32 [num_features, num_cuts] row-major)
 * so WriteRaw can compute bin codes natively during the build pass */
int DmlcTpuBinnedCacheWriterSetCuts(DmlcTpuBinnedCacheWriterHandle handle,
                                    const float* cuts, uint64_t num_features,
                                    uint64_t num_cuts);
/* bin + pack + append one block from raw CSR arrays (label/weight f32[rows],
 * row_ptr i32[rows+1], index i32[nnz], value f32[nnz]; qid i32[rows] or
 * NULL).  Bin codes replicate QuantileBinner.transform_entries bit-exactly;
 * the presence mask is (v != 0) && !isnan(v). */
int DmlcTpuBinnedCacheWriterWriteRaw(DmlcTpuBinnedCacheWriterHandle handle,
                                     uint32_t part_id, uint32_t seq,
                                     uint64_t rows, uint64_t nnz,
                                     const float* label, const float* weight,
                                     const int32_t* row_ptr,
                                     const int32_t* index, const float* value,
                                     const int32_t* qid);
/* select the block codec (block_codec.h id: 0 raw, 1 bitshuffle+LZ4) for
 * subsequent WriteBlock/WriteRaw calls; incompressible blocks silently
 * stay raw (per-record cflag 0), so bit-identity never depends on
 * compressibility */
int DmlcTpuBinnedCacheWriterSetCodec(DmlcTpuBinnedCacheWriterHandle handle,
                                     int codec);
/* write the part map and patch the header sentinels (LAST, so a crash
 * before this leaves an invalid cache that readers reject) */
int DmlcTpuBinnedCacheWriterClose(DmlcTpuBinnedCacheWriterHandle handle);
void DmlcTpuBinnedCacheWriterFree(DmlcTpuBinnedCacheWriterHandle handle);
/* open never fails on a bad cache: *out is a handle whose Valid reports 0
 * and whose Error says why (missing/torn/truncated/version skew).
 * recover != 0 resyncs past corrupt block spans (record.corrupt_skipped). */
int DmlcTpuBinnedCacheReaderCreate(const char* uri, int recover,
                                   DmlcTpuBinnedCacheReaderHandle* out);
int DmlcTpuBinnedCacheReaderValid(DmlcTpuBinnedCacheReaderHandle handle,
                                  int* out);
/* 1 when no file existed at all (first build, not a rebuild) */
int DmlcTpuBinnedCacheReaderMissing(DmlcTpuBinnedCacheReaderHandle handle,
                                    int* out);
/* why Valid == 0; pointer valid while the handle lives */
int DmlcTpuBinnedCacheReaderError(DmlcTpuBinnedCacheReaderHandle handle,
                                  const char** out);
int DmlcTpuBinnedCacheReaderMetaJson(DmlcTpuBinnedCacheReaderHandle handle,
                                     const char** out);
int DmlcTpuBinnedCacheReaderPartMapJson(DmlcTpuBinnedCacheReaderHandle handle,
                                        const char** out);
/* next block record: 1 = *data/*size borrowed until the next call on this
 * handle, 0 = end of blocks, -1 = error */
int DmlcTpuBinnedCacheReaderNextBlock(DmlcTpuBinnedCacheReaderHandle handle,
                                      const void** data, uint64_t* size);
/* next block as a zero-copy view (doc/binned_cache.md "Zero-copy hit
 * path"): 1 = got a block, 0 = end of blocks, -1 = error.  *borrowed = 1
 * means *data points into the reader's mapping/arena and stays valid until
 * the handle is freed; *borrowed = 0 means an internal scratch buffer
 * (streamed or reassembled split record) valid only until the next call —
 * copy it before advancing.  Bytes served through borrowed views are never
 * copied host-side (cache.bytes_copied counts every non-borrowed byte). */
int DmlcTpuBinnedCacheReaderNextBlockView(
    DmlcTpuBinnedCacheReaderHandle handle, const void** data, uint64_t* size,
    int* borrowed);
/* arena backing the last NextBlockView result when that record was
 * compressed and decoded (doc/binned_cache.md "Block codec"): *out is the
 * CacheArenaPool arena the decoded view points into and ownership moves to
 * the caller (release with DmlcTpuCacheArenaRelease when the view is no
 * longer referenced); *out = NULL when the last view was raw.  Untaken
 * decode arenas are recycled on the next NextBlockView call. */
int DmlcTpuBinnedCacheReaderTakeArena(DmlcTpuBinnedCacheReaderHandle handle,
                                      void** out);
/* toggle inline decode (default 1).  At 0, NextBlock/NextBlockView return
 * records exactly as stored, compressed payloads included — the staging
 * dataservice worker's serve mode: wire frames ship stored bytes verbatim
 * and the client decodes (DmlcTpuBinnedBlockDecode). */
int DmlcTpuBinnedCacheReaderSetDecode(DmlcTpuBinnedCacheReaderHandle handle,
                                      int decode);
/* read backend this open resolved to: 0 stream, 1 mmap, 2 O_DIRECT arena */
int DmlcTpuBinnedCacheReaderBackend(DmlcTpuBinnedCacheReaderHandle handle,
                                    int* out);
/* jump the block cursor to a part's first-record offset (part map) */
int DmlcTpuBinnedCacheReaderSeekTo(DmlcTpuBinnedCacheReaderHandle handle,
                                   uint64_t offset);
int DmlcTpuBinnedCacheReaderBeforeFirst(DmlcTpuBinnedCacheReaderHandle handle);
int64_t DmlcTpuBinnedCacheReaderCorruptSkipped(
    DmlcTpuBinnedCacheReaderHandle handle);
void DmlcTpuBinnedCacheReaderFree(DmlcTpuBinnedCacheReaderHandle handle);
/* 4 KiB-aligned host staging arena from the process-wide recycling pool
 * (CacheArenaPool): capacity >= size, rounded to a power-of-two bucket.
 * Release returns it for reuse (cache.arena_reuse) or frees it when the
 * pool is at its DMLCTPU_BINCACHE_ARENA_MB cap; callable from any thread. */
int DmlcTpuCacheArenaAcquire(uint64_t size, void** out);
int DmlcTpuCacheArenaRelease(void* ptr);

/* ---- block codec (cpp/src/data/block_codec.h) ---------------------------
 * Dependency-free bitshuffle+LZ4 block compression for binned cache
 * records (doc/binned_cache.md "Block codec").  Codec ids are on-disk
 * format: 0 raw, 1 lz4, 2 reserved for zstd. */
/* 1 when compression codecs are compiled in (-DDMLCTPU_CODEC=1, default);
 * 0 in a compiled-out build, where every write falls back to raw */
int DmlcTpuBlockCodecEnabled(void);
/* codec id for a knob spelling ("raw"/"lz4"); -1 unknown or not built in */
int DmlcTpuBlockCodecFromName(const char* name);
/* canonical name for a codec id ("raw"/"lz4"/"zstd"/"unknown"; static) */
const char* DmlcTpuBlockCodecName(int codec);
/* worst-case Encode output size for n input bytes */
uint64_t DmlcTpuBlockCodecBound(uint64_t n);
/* compress n bytes into out (cap >= Bound(n)): returns the compressed
 * size, 0 when incompressible (store raw), -1 on error */
int64_t DmlcTpuBlockCodecEncode(int codec, const void* in, uint64_t n,
                                void* out, uint64_t cap);
/* decompress n bytes into exactly raw_len bytes at out; bounds-checked —
 * truncated or bit-flipped input returns -1, never overreads.  0 on ok. */
int64_t DmlcTpuBlockCodecDecode(int codec, const void* in, uint64_t n,
                                void* out, uint64_t raw_len);
/* decode one maybe-compressed block record payload (header + columns, as
 * served by NextBlockView on a remote worker or read off the 0xff9a wire):
 * when the payload is compressed, *arena is a CacheArenaPool arena holding
 * [header cflag=0][raw columns] of *out_size bytes — ownership moves to
 * the caller (DmlcTpuCacheArenaRelease).  When it is already raw, *arena =
 * NULL and *out_size = size: the caller keeps its own buffer.  -1 on
 * corrupt payloads (bad codec id, length contradiction, decode failure). */
int DmlcTpuBinnedBlockDecode(const void* payload, uint64_t size, void** arena,
                             uint64_t* out_size);

/* ---- telemetry (dmlctpu/telemetry.h) ------------------------------------- */
/* *out = 1 when telemetry was compiled in (DMLCTPU_TELEMETRY=1), else 0.
 * With it compiled out every call below degrades to a cheap no-op:
 * snapshots report {"enabled":false}, counters read 0, traces are empty. */
int DmlcTpuTelemetryEnabled(int* out);
/* JSON snapshot of every registered counter/gauge/histogram; pointer valid
 * until the next telemetry call on the same thread. */
int DmlcTpuTelemetrySnapshotJson(const char** out);
/* zero every registered metric (objects stay registered). */
int DmlcTpuTelemetryReset(void);
/* add delta to the named process-wide counter (creates it on first use) —
 * how the Python staging loop publishes H2D feed occupancy. */
int DmlcTpuTelemetryCounterAdd(const char* name, int64_t delta);
/* read the named counter into *out (creates it as 0 on first use). */
int DmlcTpuTelemetryCounterGet(const char* name, int64_t* out);
/* start/stop buffering trace spans (start clears prior spans). */
int DmlcTpuTelemetryTraceStart(void);
int DmlcTpuTelemetryTraceStop(void);
/* Chrome trace-event JSON of the buffered spans; pointer valid until the
 * next telemetry call on the same thread. */
int DmlcTpuTelemetryTraceDumpJson(const char** out);
/* record one complete span (steady-clock microseconds, e.g. from Python's
 * time.monotonic_ns()//1000) into the active trace. */
int DmlcTpuTelemetryRecordSpan(const char* name, int64_t ts_us,
                               int64_t dur_us);
/* the same, under the lineage id of the batch the span handled (>= 0): it
 * goes out as args.lineage whether or not a trace context is set. */
int DmlcTpuTelemetryRecordSpanLineage(const char* name, int64_t ts_us,
                                      int64_t dur_us, int64_t lineage);
/* the same, and one call closes the span in the registry too: tracing on or
 * off, dur_us is added to the counter `total` (NULL or "": none) and, when
 * main_outermost != 0, to main.span_us — the binding's span() says whether
 * the span was the outermost one open on the main thread. */
int DmlcTpuTelemetryRecordSpanTotal(const char* name, int64_t ts_us,
                                    int64_t dur_us, int64_t lineage,
                                    const char* total, int main_outermost);
/* set/adjust/read the named process-wide gauge (created on first use) —
 * how the Python staging loop publishes H2D queue depth for the flight
 * recorder. */
int DmlcTpuTelemetryGaugeSet(const char* name, int64_t value);
int DmlcTpuTelemetryGaugeAdd(const char* name, int64_t delta);
int DmlcTpuTelemetryGaugeGet(const char* name, int64_t* out);
/* Install the process-ambient distributed trace context: spans recorded
 * while trace_id != 0 carry (trace_id, parent_span, lineage) into the trace
 * dump as Chrome-trace args, so tracker.job_trace() can link them causally
 * under the originating client span (doc/observability.md "Distributed
 * tracing").  trace_id = 0 clears the context.  A no-op when telemetry is
 * compiled out. */
int DmlcTpuTelemetrySetTraceContext(uint64_t trace_id, uint64_t parent_span,
                                    int64_t lineage);
/* read the ambient context back (outputs may be NULL; zeros / -1 when no
 * context is installed or telemetry is compiled out). */
int DmlcTpuTelemetryGetTraceContext(uint64_t* trace_id, uint64_t* parent_span,
                                    int64_t* lineage);
/* Validate that `json` is one complete well-formed JSON value (arbitrary
 * nesting) with nothing but whitespace after it, using the same pull reader
 * (dmlctpu/json.h) the native loaders trust.  *out_ok = 1 valid / 0 invalid;
 * the return value only reports API-level failure.  Used by the check.sh
 * jobtrace tier to vet merged /jobtrace documents. */
int DmlcTpuJsonValidate(const char* json, int* out_ok);

/* ---- stall watchdog + flight recorder (dmlctpu/watchdog.h) ---------------- */
/* (Re)arm the watchdog: fire when NO pipeline progress counter moves for
 * deadline_ms.  poll_ms=0 derives the sampling period from the deadline.
 * abort_on_stall=0 logs an ERROR and re-arms; nonzero dumps then aborts the
 * process.  dump_path NULL/"" writes the flight record to the log sink only.
 * All of this degrades to a no-op when telemetry is compiled out. */
int DmlcTpuWatchdogStart(int64_t deadline_ms, int64_t poll_ms,
                         int abort_on_stall, const char* dump_path);
int DmlcTpuWatchdogStop(void);
int DmlcTpuWatchdogRunning(int* out);
/* stalls detected since process start (survives arm/disarm cycles). */
int DmlcTpuWatchdogStallCount(int64_t* out);
/* Build a flight record now (stalled stage, per-stage progress ages, full
 * registry snapshot, trace dump); pointer valid until the next telemetry
 * call on the same thread. */
int DmlcTpuFlightRecordJson(const char* reason, const char** out);
/* the record dumped by the most recent watchdog stall ("" when none). */
int DmlcTpuWatchdogLastRecordJson(const char** out);

/* ---- time-series sampler (dmlctpu/timeseries.h) --------------------------- */
/* (Re)arm the background sampler: every registered counter/gauge is sampled
 * into fixed-size two-resolution rings (fine ticks + coarse rollups) so
 * windowed rates stay derivable in bounded memory however long the process
 * lives.  Any arg <=0 falls back to its DMLCTPU_TS_* env knob / built-in
 * default (tick 1000 ms, 600 fine slots, 30 ticks per rollup, 960 coarse
 * slots).  Arming also installs the crash-forensics black box (fatal-log
 * hook + SIGABRT/SIGTERM flight dump).  No-op when telemetry is compiled
 * out. */
int DmlcTpuTimeseriesStart(int64_t tick_ms, int64_t fine_slots,
                           int64_t coarse_every, int64_t coarse_slots);
int DmlcTpuTimeseriesStop(void);
int DmlcTpuTimeseriesActive(int* out);
/* take one synchronous sample tick (deterministic ring driving for tests). */
int DmlcTpuTimeseriesSample(void);
/* Full dump: {"enabled","active","tick_ms",...,"series":{name:{"kind",
 * "rate_per_s","fine":[[t_us,v]...],"coarse":[[t_us,v]...]}}}; pointer
 * valid until the next telemetry call on the same thread. */
int DmlcTpuTimeseriesJson(const char** out);
/* same, with each ring truncated to its newest `points` entries (<=0: 60) —
 * the bounded form that rides flight records and the metrics push. */
int DmlcTpuTimeseriesTailJson(int points, const char** out);

/* ---- deterministic fault injection (dmlctpu/fault.h) ---------------------- */
/* *out = 1 when the fault registry was compiled in (DMLCTPU_FAULTS=1, the
 * default); 0 in a -DDMLCTPU_FAULTS=0 build, where Arm with a nonempty spec
 * fails and snapshots report {"enabled":false}. */
int DmlcTpuFaultCompiledIn(int* out);
/* (Re)arm named fault points from a spec string, replacing any previous
 * arming atomically:
 *   "io.ranged.read=err@0.01;io.opener.5xx=503@0.05:n=20;seed=7"
 * Grammar per clause: <point>=<mode>@<rate>[:n=<count>][:after=<skip>];
 * modes err|eof|503|5xx|corrupt; "seed=N" reseeds the deterministic
 * decision stream.  NULL/"" disarms everything.  Malformed specs fail with
 * -1 and leave the previous arming untouched. */
int DmlcTpuFaultArm(const char* spec);
/* disarm every fault point (armed specs and hit counters reset). */
int DmlcTpuFaultDisarm(void);
/* JSON state: {"enabled":...,"armed":...,"seed":...,"points":[...]};
 * pointer valid until the next fault/telemetry call on the same thread. */
int DmlcTpuFaultSnapshotJson(const char** out);
/* total injected faults across all points since the last (re)arm. */
int DmlcTpuFaultInjectedTotal(int64_t* out);
/* Fire the named fault point once on behalf of a binding-side hop (the
 * dataservice client's connect/receive path lives in Python but is hardened
 * by the same native registry).  *out_mode receives the armed Mode when this
 * hit should fault (1=err 2=eof 3=503 4=corrupt), 0 for a clean hit.  The
 * point is created on first use, so it can be armed before or after. */
int DmlcTpuFaultFire(const char* point, int* out_mode);

/* ---- logging ------------------------------------------------------------- */
/* severity: 0=DEBUG 1=INFO 2=WARNING 3=ERROR 4=FATAL.  `where` is
 * "file:line".  The strings are only valid for the duration of the call. */
typedef void (*DmlcTpuLogCallback)(int severity, const char* where,
                                   const char* message);
/* install (or clear, with NULL) the process-wide log sink; replaces the
 * default stderr sink.  Thread-safe against concurrent logging. */
int DmlcTpuLogSetCallback(DmlcTpuLogCallback callback);
/* emit one message through the logging pipeline (honors the min-level env
 * config; severity is clamped to ERROR — FATAL raises natively and cannot
 * cross the C boundary).  Lets bindings and tests exercise the sink. */
int DmlcTpuLogEmit(int severity, const char* message);

/* ---- misc ---------------------------------------------------------------- */
/*! \brief library version string */
const char* DmlcTpuVersion(void);

#ifdef __cplusplus
}
#endif
#endif  /* DMLCTPU_C_API_H_ */
