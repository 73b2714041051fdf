from repro.data.pipeline import (  # noqa: F401
    DevicePrefetcher, InputWaits, ShardedLoader, device_array,
)
from repro.data.streaming import (  # noqa: F401
    StreamingDataset, StreamingLoader, write_contrastive_shards,
    write_shards,
)
from repro.data.synthetic import (  # noqa: F401
    ContrastiveDataset, LMDataset, PairedEmbeddingDataset,
    ZeroShotEvalDataset,
)
