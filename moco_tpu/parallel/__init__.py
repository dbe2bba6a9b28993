from moco_tpu.parallel.dist import (
    ProcessDataPartition,
    device_row_ranges,
    maybe_initialize_multihost,
)
from moco_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    batch_sharding,
    create_mesh,
    create_multislice_mesh,
    initialize_multihost,
    replicated_sharding,
    shard_batch,
)
from moco_tpu.parallel.shuffle import (
    make_permutation,
    balanced_shuffle,
    balanced_unshuffle,
    shuffle_gather,
    unshuffle_gather,
)
from moco_tpu.parallel.ring_attention import ring_attention

__all__ = [
    "DATA_AXIS",
    "MODEL_AXIS",
    "ProcessDataPartition",
    "device_row_ranges",
    "maybe_initialize_multihost",
    "batch_sharding",
    "create_mesh",
    "create_multislice_mesh",
    "initialize_multihost",
    "replicated_sharding",
    "shard_batch",
    "make_permutation",
    "balanced_shuffle",
    "balanced_unshuffle",
    "shuffle_gather",
    "unshuffle_gather",
    "ring_attention",
]
