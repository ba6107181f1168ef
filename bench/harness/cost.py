"""Operations the measured work requires, computed from shapes.

The counts follow the paper's accounting (Table 5, App. G), copied here so
that no change to the program can move them: a linear layer costs
``2 * d_in * d_out * density`` operations per token (a sparse layer its
``2 * nnz``), the backward pass twice the forward. Attention's score and
value products are added (the paper's count leaves them out): ``4 * heads *
head_dim * context`` per token and layer. Work that a path does beyond this,
such as the masked path's dense products on sparse layers or recomputation
under remat, does not count.
"""
from __future__ import annotations

import dataclasses


@dataclasses.dataclass(frozen=True)
class LinearCost:
    name: str
    d_in: int
    d_out: int
    density: float = 1.0     # fraction of weights active
    n_replicas: int = 1      # stacked layers

    @property
    def nnz(self) -> float:
        return self.d_in * self.d_out * self.density * self.n_replicas

    def fwd_flops_per_token(self) -> float:
        return 2.0 * self.nnz


def sparse_stacks(model: dict) -> dict[str, tuple[int, int, int]]:
    """Stack name -> (d_in, d_out, fan_in k) of each sparse linear."""
    d, ff = model["hidden_size"], model["intermediate_size"]
    q_dim = model["num_attention_heads"] * model["head_dim"]
    k = model["sparsity"]["fan_in"]
    dims = {"wo": (q_dim, d), "w_gate": (d, ff), "w_up": (d, ff),
            "w_down": (ff, d)}
    return {name: (*dims[name], int(k[name])) for name in dims}


def linears(model: dict) -> list[LinearCost]:
    """Every linear of the model: the sparse stacks at their fan-in, the
    dense attention projections, and the (tied) output head."""
    n = model["num_hidden_layers"]
    d = model["hidden_size"]
    hd = model["head_dim"]
    out = [LinearCost(name, d_in, d_out, k / d_in, n)
           for name, (d_in, d_out, k) in sparse_stacks(model).items()]
    out += [LinearCost("wq", d, model["num_attention_heads"] * hd, 1.0, n),
            LinearCost("wk", d, model["num_key_value_heads"] * hd, 1.0, n),
            LinearCost("wv", d, model["num_key_value_heads"] * hd, 1.0, n)]
    return out


def head(model: dict) -> LinearCost:
    return LinearCost("head", model["hidden_size"], model["vocab_size"])


def attention_flops(model: dict, context_sum: float) -> float:
    """Score and value products of all layers, summed over tokens whose
    contexts (positions attended, the token itself included) add up to
    ``context_sum``."""
    return (4.0 * model["num_hidden_layers"] * model["num_attention_heads"]
            * model["head_dim"] * context_sum)


def block_flops_per_token(model: dict) -> float:
    return sum(l.fwd_flops_per_token() for l in linears(model))


def train_flops_per_step(model: dict, batch: int, seq: int) -> float:
    """Forward and backward (3x forward) of ``batch`` causal rows of
    ``seq`` tokens, the head at every token."""
    fwd = (batch * seq * (block_flops_per_token(model)
                          + head(model).fwd_flops_per_token())
           + attention_flops(model, batch * seq * (seq + 1) / 2))
    return 3.0 * fwd


def sparse_nnz(model: dict) -> float:
    n = model["num_hidden_layers"]
    return sum(n * d_out * k for _, d_out, k in sparse_stacks(model).values())
