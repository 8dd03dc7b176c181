"""quiver_tpu — TPU-native graph-learning data engine.

Ground-up JAX/XLA re-design of torch-quiver (reference public API:
srcs/python/quiver/__init__.py:2-17): GPU-class k-hop neighbor sampling over
CSR topology, a tiered feature cache (chip HBM -> ICI peers -> host DRAM ->
mmap disk), and multi-chip/multi-host scaling over ICI/DCN meshes.
"""

from .feature import DeviceConfig, DistFeature, Feature, PartitionInfo
from .shard_tensor import Offset, ShardTensor, ShardTensorConfig
from .utils import (
    CSRTopo,
    IciTopo,
    Topo,
    can_device_access_peer,
    init_p2p,
    p2pCliqueTopo,
    parse_size,
    reindex_by_config,
    reindex_feature,
    show_tensor_info,
)
from . import inference
from .partition import (
    load_quiver_feature_partition,
    partition_feature_without_replication,
    quiver_partition_feature,
)
from . import comm, obs, pyg, tiers, trace
from . import quant
from . import lifecycle
from . import serve
from . import stream
from . import workloads
from .lifecycle import CompactionPolicy, ProvisionPolicy, RetentionPolicy
from .stream import GraphDelta, StreamingAdjacency, StreamingTiledGraph
from .tiers import DiskShard, PlacementPlan, TierPlacement, TierStore
from .quant import QuantizedFeature
from .serve import DistServeConfig, DistServeEngine, ServeConfig, ServeEngine
from .comm import HostRankTable, NcclComm, TpuComm, getNcclId
from .pipeline import (
    AsyncReadPool,
    TieredBatch,
    TieredFeaturePipeline,
    TrainPipeline,
    make_tiered_train_step,
    tiered_lookup,
)

__version__ = "0.1.0"

__all__ = [
    "CSRTopo",
    "DeviceConfig",
    "DistFeature",
    "Feature",
    "HostRankTable",
    "IciTopo",
    "NcclComm",
    "TpuComm",
    "comm",
    "getNcclId",
    "obs",
    "trace",
    "Offset",
    "PartitionInfo",
    "ShardTensor",
    "ShardTensorConfig",
    "Topo",
    "can_device_access_peer",
    "init_p2p",
    "load_quiver_feature_partition",
    "p2pCliqueTopo",
    "parse_size",
    "partition_feature_without_replication",
    "pyg",
    "quant",
    "QuantizedFeature",
    "serve",
    "stream",
    "workloads",
    "lifecycle",
    "CompactionPolicy",
    "ProvisionPolicy",
    "RetentionPolicy",
    "GraphDelta",
    "StreamingAdjacency",
    "StreamingTiledGraph",
    "DistServeConfig",
    "DistServeEngine",
    "ServeConfig",
    "ServeEngine",
    "inference",
    "quiver_partition_feature",
    "reindex_by_config",
    "reindex_feature",
    "show_tensor_info",
    "AsyncReadPool",
    "DiskShard",
    "PlacementPlan",
    "TierPlacement",
    "TierStore",
    "tiers",
    "TieredBatch",
    "TieredFeaturePipeline",
    "TrainPipeline",
    "make_tiered_train_step",
    "tiered_lookup",
]
