"""Multi-chip / multi-host parallelism over jax.sharding meshes."""

from .collectives import (
    pad_to_multiple,
    sharded_gather,
    sharded_gather_a2a,
    sharded_gather_grouped,
)
from .topology import (
    ShardedTopology,
    sampling_comm_bytes,
    shard_topology_rows,
    sharded_sample_layer,
    sharded_sample_layer_grouped,
)
from .collectives import sharded_gather_hot_cold
from .scaling import (
    collective_payload_bytes,
    predict_layout,
    products_scaling_table,
)
from .train import (
    calibrate_cold_budget,
    make_mesh,
    make_sharded_topo_sample,
    make_sharded_topo_train_step,
    make_sharded_train_step,
    mesh_axes,
    replicate,
    shard_feature_hot_cold,
    shard_feature_rows,
    step_comm_bytes,
)

__all__ = [
    "ShardedTopology",
    "calibrate_cold_budget",
    "collective_payload_bytes",
    "predict_layout",
    "products_scaling_table",
    "make_mesh",
    "make_sharded_topo_sample",
    "make_sharded_topo_train_step",
    "make_sharded_train_step",
    "mesh_axes",
    "pad_to_multiple",
    "replicate",
    "sampling_comm_bytes",
    "shard_feature_hot_cold",
    "shard_feature_rows",
    "sharded_gather_hot_cold",
    "shard_topology_rows",
    "sharded_gather",
    "step_comm_bytes",
    "sharded_gather_a2a",
    "sharded_gather_grouped",
    "sharded_sample_layer",
    "sharded_sample_layer_grouped",
]
