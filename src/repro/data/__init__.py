from repro.data.recsys import RecsysDataset, make_recsys, recsys_graph
from repro.data.synthetic import SyntheticGraphDataset, rmat_graph

__all__ = [
    "RecsysDataset",
    "SyntheticGraphDataset",
    "make_recsys",
    "recsys_graph",
    "rmat_graph",
]
