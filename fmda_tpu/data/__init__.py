from fmda_tpu.data.source import (
    ArraySource, FeatureSource, TokenArraySource, TokenSource)
from fmda_tpu.data.windows import chunk_ranges, train_val_test_split, window_index_matrix
from fmda_tpu.data.normalize import (
    NormParams,
    chunk_norm_params,
    load_norm_params,
    normalize,
    save_norm_params,
)
from fmda_tpu.data.pipeline import (
    ChunkDataset,
    TokenBatches,
    TokenDataset,
    WindowBatches,
    background_compose,
    prefetch_batches,
)

__all__ = [
    "ArraySource",
    "FeatureSource",
    "TokenArraySource",
    "TokenSource",
    "chunk_ranges",
    "train_val_test_split",
    "window_index_matrix",
    "NormParams",
    "chunk_norm_params",
    "normalize",
    "save_norm_params",
    "load_norm_params",
    "ChunkDataset",
    "TokenBatches",
    "TokenDataset",
    "WindowBatches",
    "background_compose",
    "prefetch_batches",
]
