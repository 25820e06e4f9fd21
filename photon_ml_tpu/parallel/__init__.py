"""Distributed runtime: meshes, sharded objectives, feature-axis sharding.

The XLA-collective replacement for the reference's Spark layer (SURVEY
sect. 2.4): psum = treeAggregate, replicated sharding = broadcast,
all_to_all/sorts = shuffle.
"""

from photon_ml_tpu.parallel.mesh import (
    DATA_AXIS,
    MODEL_AXIS,
    data_sharding,
    make_mesh,
    replicate,
    replicated,
    shard_batch,
)
from photon_ml_tpu.parallel.multihost import (
    initialize_multihost,
    is_coordinator,
    process_count,
    process_index,
    process_shard,
    sync_processes,
)
from photon_ml_tpu.parallel.shuffle import (
    ShuffledRows,
    entity_all_to_all,
    reshard_capacity,
)
from photon_ml_tpu.parallel.distributed import (
    FeatureShardedSparseBatch,
    feature_shard_sparse_batch,
)

__all__ = [
    "DATA_AXIS",
    "MODEL_AXIS",
    "data_sharding",
    "make_mesh",
    "replicate",
    "replicated",
    "shard_batch",
    "initialize_multihost",
    "is_coordinator",
    "process_count",
    "process_index",
    "process_shard",
    "sync_processes",
    "ShuffledRows",
    "entity_all_to_all",
    "reshard_capacity",
    "FeatureShardedSparseBatch",
    "feature_shard_sparse_batch",
]
