"""Weights and SRigL masks made by the benchmark from ``--seed``.

Everything here is made on the device in one jitted call, in the layout the
program's dense transformer takes (``params["blocks"][<name>]`` stacked over
layers, ``masks["blocks"][<stack>]``), and in the storage type the cell
states. The plain reference reads the same arrays, so the two are compared
on one set of weights that neither made.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


# the program's RMSNorm multiplies by (1 + scale); the reference by its
# published weight, so it reads 1 + the stored scale
NORM_SCALE_STD = 0.1
EMBED_STD = 0.02


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key for any non-negative seed, also one over 32 bits."""
    seed = int(seed)
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(0)
    while True:
        key = jax.random.fold_in(key, seed & 0x7FFFFFFF)
        seed >>= 31
        if not seed:
            return key


def layout(model: dict) -> dict[str, tuple[tuple[int, ...], str]]:
    """Leaf name -> (shape, kind) of the block stack, kind one of
    "norm", "dense", "sparse"."""
    n, d, hd = (model["num_hidden_layers"], model["hidden_size"],
                model["head_dim"])
    qd = model["num_attention_heads"] * hd
    kvd = model["num_key_value_heads"] * hd
    ff = model["intermediate_size"]
    return {
        "ln1": ((n, d), "norm"), "ln2": ((n, d), "norm"),
        "q_norm": ((n, hd), "norm"), "k_norm": ((n, hd), "norm"),
        "wq": ((n, d, qd), "dense"), "wk": ((n, d, kvd), "dense"),
        "wv": ((n, d, kvd), "dense"), "wo": ((n, qd, d), "sparse"),
        "w_gate": ((n, d, ff), "sparse"), "w_up": ((n, d, ff), "sparse"),
        "w_down": ((n, ff, d), "sparse"),
    }


def _constant_fan_in(key, d_in: int, d_out: int, k: int) -> jax.Array:
    """(d_in, d_out) bool: every output column has exactly k inputs."""
    scores = jax.random.uniform(key, (d_out, d_in))
    _, idx = jax.lax.top_k(scores, k)
    rows = jnp.arange(d_out)[:, None]
    return jnp.zeros((d_out, d_in), bool).at[rows, idx].set(True).T


def _per_layer(fn, key, n: int):
    """Stack ``fn(key_i)`` over n layers one layer at a time (lax.map), so
    temporaries stay at one layer's size."""
    return jax.lax.map(fn, jax.random.split(key, n))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _make(model_items: tuple, dtype: str, key):
    model = unfreeze(model_items)
    dt = jnp.dtype(dtype)
    fan = model["sparsity"]["fan_in"]
    vocab, d = model["vocab_size"], model["hidden_size"]
    keys = iter(jax.random.split(key, 64))
    params = {"embed": (jax.random.normal(next(keys), (vocab, d))
                        * EMBED_STD).astype(dt),
              "final_norm": (jax.random.normal(next(keys), (d,))
                             * NORM_SCALE_STD).astype(dt)}
    blocks, masks = {}, {}
    for name, (shape, kind) in layout(model).items():
        k_val, k_mask = next(keys), next(keys)
        n = shape[0]
        if kind == "norm":
            blocks[name] = (jax.random.normal(k_val, shape)
                            * NORM_SCALE_STD).astype(dt)
            continue
        std = 1.0 / np.sqrt(fan[name] if kind == "sparse" else shape[1])
        blocks[name] = _per_layer(
            lambda k: (jax.random.normal(k, shape[1:]) * std).astype(dt),
            k_val, n)
        if kind == "sparse":
            masks[name] = _per_layer(
                lambda k: _constant_fan_in(k, shape[1], shape[2],
                                           int(fan[name])), k_mask, n)
    params["blocks"] = blocks
    return params, {"blocks": masks}


def freeze(obj):
    if isinstance(obj, dict):
        return tuple(sorted((k, freeze(v)) for k, v in obj.items()))
    if isinstance(obj, list):
        return ("__list__",) + tuple(freeze(v) for v in obj)
    return obj


def unfreeze(obj):
    if isinstance(obj, tuple) and obj and obj[0] == "__list__":
        return [unfreeze(v) for v in obj[1:]]
    if isinstance(obj, tuple) and all(isinstance(i, tuple) and len(i) == 2
                                      and isinstance(i[0], str) for i in obj):
        return {k: unfreeze(v) for k, v in obj}
    return obj


def model_keys(model: dict) -> dict:
    """The parts of a configuration that shape the weights."""
    keep = ("num_hidden_layers", "hidden_size", "head_dim",
            "num_attention_heads", "num_key_value_heads", "intermediate_size",
            "vocab_size")
    out = {k: model[k] for k in keep}
    out["sparsity"] = {"fan_in": dict(model["sparsity"]["fan_in"])}
    return out


def make(model: dict, dtype: str, seed: int):
    """(params, masks) on the default device, stored as ``dtype``."""
    return _make(freeze(model_keys(model)), dtype, key_from_seed(seed))
