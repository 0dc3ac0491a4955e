"""gat-arxiv [arXiv:1710.10903; DGL's ogbn-arxiv GAT example]: 3 layers of
3 heads x 250 (a last layer of 3 heads x 40, averaged, plus a bias), a
residual projection in every layer, symmetric degree normalisation, self
loops, ReLU between layers."""
from .base import ArchSpec, register, GNN_SHAPES
from .families import GNNBundle

MODEL_KW = {"d_hidden": 250, "n_heads": 3, "n_layers": 3, "final_heads": 3,
            "residual": True, "final_bias": True, "activation": "relu",
            "self_loops": True, "norm": True}
REDUCED = dict(MODEL_KW, d_hidden=8, classes=4)

SPEC = register(ArchSpec(
    name="gat-arxiv", family="gnn", shapes=tuple(GNN_SHAPES),
    build=lambda: GNNBundle("gat", MODEL_KW, n_classes=40)))
