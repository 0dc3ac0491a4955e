"""Model FLOPs of one full-graph GCN training step, from the shapes alone.

Per layer ``i`` with ``N`` nodes, ``E`` edges and widths ``d_in -> d_out``,
in the model's own order ``A_hat H W``:

* forward: the aggregation's additions, ``(E + N) * d_in`` (one per edge
  and one for the self loop, per feature), and the update matmul,
  ``2 * N * d_in * d_out``;
* backward: the weight gradient, ``2 * N * d_in * d_out``; for every layer
  but the first, also the input gradient's matmul, ``2 * N * d_in * d_out``,
  and the transposed aggregation, ``(E + N) * d_in``.  The first layer's
  input gradient is never needed and is not counted.

Scalings, bias, activation, the loss and the optimizer are elementwise and
are left out; recomputed work is not counted.
"""
from __future__ import annotations

from typing import Sequence


def gcn_train_step(num_nodes: int, num_edges: int,
                   dims: Sequence[int]) -> int:
    n, e = int(num_nodes), int(num_edges)
    total = 0
    for i, (d_in, d_out) in enumerate(zip(dims[:-1], dims[1:])):
        agg = (e + n) * d_in
        mm = 2 * n * d_in * d_out
        total += agg + mm + mm                  # forward, weight gradient
        if i > 0:
            total += mm + agg                   # input gradient
    return total
