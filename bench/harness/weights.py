"""Weights and SRigL masks made by the benchmark from ``--seed``.

Everything here is made on the device in one jitted call, in the layout the
configuration's architecture module gives (``harness.archs``: every leaf's
path, leading dims and kind) and in the storage type the cell states. A
sparse matrix gets a mask of constant fan-in, one per index of its leading
dims: ``(L,)`` for a stack of layers, ``(L, E)`` for a stack of expert
layers. The plain reference reads the same arrays, so the two are compared
on one set of weights that neither made.
"""
from __future__ import annotations

import functools
import math
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from harness import archs

# the program's RMSNorm multiplies by (1 + scale); the reference by its
# published weight, so it reads 1 + the stored scale
NORM_SCALE_STD = 0.1
EMBED_STD = 0.02


class Leaf(NamedTuple):
    lead: tuple[int, ...]       # leading (stack) dims: (L,), (L, E) or ()
    shape: tuple[int, ...]      # one vector's or one matrix's (d_in, d_out)
    kind: str                   # "embed", "norm", "dense" or "sparse"
    fan_in: int = 0             # a sparse matrix's constant fan-in


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


def layout(model: dict) -> dict[tuple[str, ...], Leaf]:
    return archs.of(model).layout(model)


def fan_ins(model: dict) -> dict[tuple[str, ...], int]:
    """Mask path -> constant fan-in, for every sparse matrix."""
    return {path: leaf.fan_in for path, leaf in layout(model).items()
            if leaf.kind == "sparse"}


def _constant_fan_in(key, d_in: int, d_out: int, k: int) -> jax.Array:
    """(d_in, d_out) bool: every output column has exactly k inputs."""
    scores = jax.random.uniform(key, (d_out, d_in))
    _, idx = jax.lax.top_k(scores, k)
    rows = jnp.arange(d_out)[:, None]
    return jnp.zeros((d_out, d_in), bool).at[rows, idx].set(True).T


def _per_matrix(fn, key, lead):
    """``fn(key_i)`` for each index of the leading dims, one at a time
    (lax.map), so temporaries stay at one matrix's size."""
    out = jax.lax.map(fn, jax.random.split(key, math.prod(lead)))
    return out.reshape(*lead, *out.shape[1:])


def _n_keys(leaf: Leaf) -> int:
    """A stacked leaf draws a values key and a masks key whatever its kind,
    an unstacked one a values key (and a masks key where sparse): the order
    in which the first layout drew them, kept so that a seed's weights stay
    what they were."""
    return 2 if leaf.lead or leaf.kind == "sparse" else 1


def _set(tree: dict, path: tuple, leaf) -> None:
    for p in path[:-1]:
        tree = tree.setdefault(p, {})
    tree[path[-1]] = leaf


@functools.partial(jax.jit, static_argnums=(0, 1))
def _make(leaves: tuple, dtype: str, key):
    dt = jnp.dtype(dtype)
    keys = iter(jax.random.split(key, sum(_n_keys(l) for _, l in leaves)))
    params, masks = {}, {}
    for path, leaf in leaves:
        k_val = next(keys)
        k_mask = next(keys) if _n_keys(leaf) == 2 else None
        if leaf.kind in ("embed", "norm"):
            std = EMBED_STD if leaf.kind == "embed" else NORM_SCALE_STD
            _set(params, path, (jax.random.normal(
                k_val, leaf.lead + leaf.shape) * std).astype(dt))
            continue
        d_in, d_out = leaf.shape
        std = 1.0 / np.sqrt(leaf.fan_in if leaf.kind == "sparse" else d_in)
        value = lambda k: (jax.random.normal(k, leaf.shape) * std).astype(dt)
        mask = lambda k: _constant_fan_in(k, d_in, d_out, leaf.fan_in)
        if leaf.lead:
            value = functools.partial(_per_matrix, value, lead=leaf.lead)
            mask = functools.partial(_per_matrix, mask, lead=leaf.lead)
        _set(params, path, value(k_val))
        if leaf.kind == "sparse":
            _set(masks, path, mask(k_mask))
    return params, masks


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


def make(model: dict, dtype: str, seed: int):
    """(params, masks) on the default device, stored as ``dtype``."""
    return _make(tuple(layout(model).items()), dtype, key_from_seed(seed))
