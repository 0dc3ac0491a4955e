"""Model FLOPs of one full-graph GAT training step, from the shapes alone.

Per layer ``i`` with ``N`` nodes, ``E`` edges plus ``N`` self loops
(``E' = E + N``), input width ``d_in`` and ``H`` heads of ``F`` features
(``HF = H * F``), counted as ``flops.py`` counts GCN's:

* forward: the projections ``h W`` and the residual ``h R``,
  ``2 * N * d_in * HF`` each; the score dot-products ``z a_src`` and
  ``z a_dst``, ``2 * N * HF`` each; per edge and head one addition for the
  score and one for the softmax's sum, ``2 * E' * H``; the weighted sum,
  ``2 * E' * HF``;
* backward: the weight gradients of both projections, ``2 * N * d_in * HF``
  each, and for every layer but the first their input gradients as well;
  both gradients (of ``z`` and of ``a``) of each score dot-product,
  ``8 * N * HF`` in all; the weighted sum's gradients of ``z`` and of the
  weights, ``4 * E' * HF``; the scores' and the softmax's sums again,
  ``2 * E' * H``.  The first layer's input gradient is never needed and is
  not counted.

Activations, exponentials, the maximum, scalings, the bias, the loss and
the optimizer are elementwise or comparisons and are left out; recomputed
work (the checkpointed gathers) is not counted.
"""
from __future__ import annotations

from typing import Sequence


def gat_train_step(num_nodes: int, num_edges: int, d_in: int,
                   heads: Sequence[int], widths: Sequence[int]) -> int:
    """``heads[i]`` and ``widths[i]`` (features per head) of layer ``i``;
    layer ``i + 1`` takes ``heads[i] * widths[i]`` inputs (concatenated)."""
    n, e = int(num_nodes), int(num_edges) + int(num_nodes)
    total = 0
    for i, (h, f) in enumerate(zip(heads, widths)):
        hf = h * f
        proj = 2 * (2 * n * d_in * hf)             # W and R
        total += proj * (3 if i > 0 else 2)         # fwd, weight(, input) grad
        total += 4 * n * hf + 8 * n * hf            # score dots, fwd + bwd
        total += 2 * e * hf + 4 * e * hf            # weighted sum, fwd + bwd
        total += 2 * e * h + 2 * e * h              # score and softmax sums
        d_in = hf
    return total
