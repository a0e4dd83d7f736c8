"""dmlc_core_tpu — a TPU-native rebuild of the dmlc-core substrate.

Layers (mirrors SURVEY.md §1, rebuilt TPU-first):
  * native C++ runtime (cpp/ → libdmlctpu.so): streams, sharded InputSplit
    with record healing, RecordIO, text parsers, prefetch pipelines;
  * `io` / `data`: Python bindings + DeviceStagingIter that pads ragged CSR
    batches into static XLA shapes resident in TPU HBM;
  * `ops` / `models`: jittable sparse compute (segment-sum CSR kernels) and
    model families (sparse linear, factorization machine);
  * `parallel`: device-mesh data parallelism, psum collectives over ICI, and
    the DMLC_* env bootstrap onto jax.distributed;
  * `tracker`: dmlc-submit job launch + rabit-compatible rendezvous.
"""
from . import (checkpoint, compile_cache, data, faultinject, io, models, ops,
               parallel, telemetry, timer)
from ._native import (NativeError, build_info as native_build_info,
                      version as native_version)
from .data import (BinnedBatch, BinnedRowIter, BinnedStagingIter,
                   DeviceStagingIter, PaddedBatch, Parser, RecordBatch,
                   RecordStagingIter, RowBlock, build_bin_cache)
from .io import (FileInfo, InputSplit, RecordIOReader, RecordIOWriter,
                 listdir, open_seek_stream, open_stream, path_info)

__version__ = "0.1.0"
__all__ = [
    "checkpoint", "compile_cache", "data", "faultinject", "io", "models",
    "ops", "parallel", "telemetry", "timer",
    "NativeError", "native_build_info", "native_version",
    "DeviceStagingIter", "PaddedBatch", "Parser", "RowBlock",
    "RecordBatch", "RecordStagingIter",
    "BinnedBatch", "BinnedRowIter", "BinnedStagingIter", "build_bin_cache",
    "InputSplit", "RecordIOReader", "RecordIOWriter",
    "FileInfo", "open_stream", "open_seek_stream", "listdir", "path_info",
]
