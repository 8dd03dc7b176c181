"""Reference-parity model zoo (GraphSAGE, GAT, GCN, R-GAT) in flax."""

from .sage import SAGEConv, GraphSAGE, masked_mean_aggregate
from .gat import GAT, GATConv
from .gcn import GCN, GCNConv
from .rgat import RGAT, RGATConv

__all__ = [
    "GAT", "GATConv", "GCN", "GCNConv", "RGAT", "RGATConv", "SAGEConv", "GraphSAGE",
    "masked_mean_aggregate",
]
