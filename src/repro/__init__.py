"""repro: production-grade JAX reproduction of Rubik (hierarchical GCN
learning: LSH graph reordering + computation reuse + hierarchical mapping),
scaled to multi-pod TPU meshes."""

__version__ = "1.0.0"
