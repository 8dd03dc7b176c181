"""PyG-style sampler API (reference srcs/python/quiver/pyg/__init__.py)."""

from .sage_sampler import (
    Adj,
    DenseAdj,
    DenseSample,
    GraphSageSampler,
    caps_from_counts,
    dense_to_pyg,
    probe_hop_counts,
    sample_and_gather_dedup,
    sample_and_gather_fused,
    sample_dense_fused,
    sample_dense_pure,
)
from .hetero_sampler import HeteroBlocks, HeteroCSRTopo, HeteroDenseSample, HeteroSampler
from .mixed_sampler import MixedGraphSageSampler, SampleJob, TrainSampleJob

__all__ = [
    "Adj",
    "DenseAdj",
    "DenseSample",
    "GraphSageSampler",
    "HeteroBlocks",
    "HeteroCSRTopo",
    "HeteroDenseSample",
    "HeteroSampler",
    "MixedGraphSageSampler",
    "SampleJob",
    "TrainSampleJob",
    "caps_from_counts",
    "dense_to_pyg",
    "probe_hop_counts",
    "sample_and_gather_dedup",
    "sample_and_gather_fused",
    "sample_dense_fused",
    "sample_dense_pure",
]
