"""Data layer: parsed RowBlocks (numpy) and TPU HBM staging."""
from .rowblock import RowBlock, Parser
from .staging import (PaddedBatch, DeviceStagingIter, PagePrefetcher,
                      RecordBatch, RecordStagingIter)
from .binned_cache import (BinnedBatch, BinnedRowIter, BinnedStagingIter,
                           build_bin_cache)

__all__ = ["RowBlock", "Parser", "PaddedBatch", "DeviceStagingIter",
           "PagePrefetcher", "RecordBatch", "RecordStagingIter", "BinnedBatch",
           "BinnedRowIter", "BinnedStagingIter", "build_bin_cache"]
