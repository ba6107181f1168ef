"""Operations the measured work requires, computed from shapes.

The counts follow the paper's accounting (Table 5, App. G), copied here so
that no change to the program can move them: a linear layer costs
``2 * d_in * d_out * density`` operations per token (a sparse layer its
``2 * nnz``), the backward pass twice the forward. Attention's score and
value products are added (the paper's count leaves them out): ``2 * heads *
(qk head + v head) * context`` per token and layer. Work that a path does
beyond this, such as the masked path's dense products on sparse layers or
recomputation under remat, does not count. Each architecture module
(``harness.archs``) sums these for its block in ``flops_per_step``.
"""
from __future__ import annotations

import math

from harness import weights as W

TRAIN_PASSES = 3.0      # forward, and a backward of twice the forward


def sparse_nnz(model: dict) -> float:
    """Non-zero weights of every sparse matrix of the layout: fan-in times
    outputs, for each index of the leading dims."""
    return float(sum(math.prod(leaf.lead) * leaf.shape[1] * leaf.fan_in
                     for leaf in W.layout(model).values()
                     if leaf.kind == "sparse"))


def causal_context(rows: int, seq: int) -> float:
    """Positions attended, summed over the tokens of ``rows`` causal rows of
    ``seq`` tokens (each token attends to itself)."""
    return rows * seq * (seq + 1) / 2


def attention_flops(n_layers: int, n_heads: int, qk_dim: int, v_dim: int,
                    context_sum: float) -> float:
    """Score (q . k) and value (p . v) products of all layers over tokens
    whose contexts add up to ``context_sum``."""
    return 2.0 * n_layers * n_heads * (qk_dim + v_dim) * context_sum


def train_flops(forward: float) -> float:
    return TRAIN_PASSES * forward
