"""Graph data for the benchmark's configurations.

A copy of the arithmetic of the repository's ``graph.datasets.synthesize``
(community structure over a power-law degree profile, duplicate edges
dropped, node ids shuffled), kept here so that a change to the program's
generator cannot change the benchmark's graphs.  With the same spec and
generator seed it draws the same random stream, so the structure, labels and
training mask equal what ``synthesize`` returns.

The structure is the dataset: each configuration fixes it with its own
generator seed.  Node features are not taken from this stream; the harness
draws them from the run's seed (:func:`features`).
"""
from __future__ import annotations

import dataclasses

import numpy as np


@dataclasses.dataclass(frozen=True)
class GraphData:
    """Edges ``src -> dst`` (int32), labels and training mask, in the
    generator's shuffled node order."""

    num_nodes: int
    src: np.ndarray
    dst: np.ndarray
    labels: np.ndarray
    train_mask: np.ndarray
    feat_dim: int
    num_classes: int

    @property
    def num_edges(self) -> int:
        return int(self.src.shape[0])

    def in_degrees(self) -> np.ndarray:
        return np.bincount(self.dst, minlength=self.num_nodes)


def _power_law_degrees(n: int, m: int, rng: np.random.Generator,
                       alpha: float = 2.1) -> np.ndarray:
    raw = rng.pareto(alpha - 1.0, size=n) + 1.0
    deg = np.maximum(1, np.round(raw * (m / raw.sum()))).astype(np.int64)
    diff = m - int(deg.sum())
    if diff > 0:
        idx = rng.integers(0, n, size=diff)
        np.add.at(deg, idx, 1)
    elif diff < 0:
        for i in np.argsort(-deg):
            take = min(deg[i] - 1, -diff)
            deg[i] -= take
            diff += take
            if diff >= 0:
                break
    return deg


def community_power_law(num_nodes: int, num_edges: int, feat_dim: int,
                        num_classes: int, community: float = 0.8,
                        seed: int = 0) -> GraphData:
    """The generator: a fraction ``community`` of each node's out-edges
    stays inside its community, the rest land uniformly; no self loops, no
    duplicate edges."""
    rng = np.random.default_rng(seed)
    n, m = num_nodes, num_edges
    k = max(2, int(np.sqrt(n / 4)))
    comm = rng.integers(0, k, size=n)
    members = {c: np.flatnonzero(comm == c) for c in range(k)}
    deg = _power_law_degrees(n, m, rng)
    base_src = np.repeat(np.arange(n, dtype=np.int64), deg)[:m]

    def sample_edges(src):
        dst = rng.integers(0, n, size=src.shape[0])
        intra = rng.random(src.shape[0]) < community
        for c in range(k):
            if members[c].size == 0:
                continue
            sel = np.flatnonzero(intra & (comm[src] == c))
            if sel.size:
                dst[sel] = rng.choice(members[c], size=sel.size)
        loops = src == dst
        dst[loops] = (dst[loops] + 1 + rng.integers(0, n - 1, loops.sum())) % n
        return src, dst

    def dedup(src, dst):
        _, first = np.unique(src * n + dst, return_index=True)
        keep = np.sort(first)
        return src[keep], dst[keep]

    src, dst = dedup(*sample_edges(base_src))
    for _ in range(6):
        deficit = m - src.shape[0]
        if deficit <= 0:
            break
        es, ed = sample_edges(rng.choice(base_src, size=int(deficit * 1.5)))
        src, dst = dedup(np.concatenate([src, es]), np.concatenate([dst, ed]))
    src, dst = src[:m], dst[:m]

    # the generator's own features: drawn only to keep the random stream in
    # step with ``synthesize``; the harness draws features from the run seed
    rng.standard_normal((n, feat_dim)).astype(np.float32)
    labels = comm % num_classes
    rng.standard_normal((num_classes, feat_dim)).astype(np.float32)
    train_mask = rng.random(n) < 0.7

    shuffle = rng.permutation(n)
    inv = np.empty_like(shuffle)
    inv[shuffle] = np.arange(n)
    return GraphData(num_nodes=n, src=inv[src].astype(np.int32),
                     dst=inv[dst].astype(np.int32),
                     labels=labels[shuffle].astype(np.int32),
                     train_mask=train_mask[shuffle], feat_dim=feat_dim,
                     num_classes=num_classes)


GENERATORS = {"community_power_law": community_power_law}


def build(spec: dict) -> GraphData:
    """The graph a configuration's ``graph`` entry describes."""
    kw = {k: v for k, v in spec.items() if k != "generator"}
    return GENERATORS[spec["generator"]](**kw)


def relabel(data: GraphData, perm: np.ndarray) -> GraphData:
    """The same graph with node ``perm[k]`` renamed ``k``.  ``perm`` must be
    a permutation of the node ids; anything else raises."""
    perm = np.asarray(perm)
    n = data.num_nodes
    if perm.shape != (n,) or not np.array_equal(np.sort(perm), np.arange(n)):
        raise ValueError("node order is not a permutation of the node ids")
    inv = np.empty(n, np.int64)
    inv[perm] = np.arange(n)
    return dataclasses.replace(
        data, src=inv[data.src].astype(np.int32),
        dst=inv[data.dst].astype(np.int32), labels=data.labels[perm],
        train_mask=data.train_mask[perm])
