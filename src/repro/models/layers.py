"""Primitive layers: norms, embeddings, RoPE/M-RoPE, sparse-aware linear apply.

All modules are functional: ``init_*`` returns a params dict, ``apply`` is a
pure function. Sparse linears take an optional boolean mask; when given, the
weight is masked with a straight-through trick so the *gradient stays dense*
(required by the RigL/SRigL grow criterion — see core/srigl.py).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.srigl import apply_mask_for_forward
from repro.sparse import formats as F


# ---------------------------------------------------------------------------
# init helpers
# ---------------------------------------------------------------------------

def dense_init(key: jax.Array, d_in: int, d_out: int, dtype=jnp.float32) -> jax.Array:
    """Truncated-normal-ish init, std = 1/sqrt(d_in)."""
    return (jax.random.normal(key, (d_in, d_out)) / jnp.sqrt(d_in)).astype(dtype)


def sparse_init(key: jax.Array, d_in: int, d_out: int, k: int, dtype=jnp.float32) -> jax.Array:
    """Fan-in-aware init for sparse layers (Evci et al. 2022): std = 1/sqrt(k).

    The dense tensor is initialized at the *sparse* fan-in scale; masked-out
    entries are dead until regrown (regrown weights start at 0 per RigL).
    """
    return (jax.random.normal(key, (d_in, d_out)) / jnp.sqrt(max(k, 1))).astype(dtype)


def embed_init(key: jax.Array, vocab: int, d: int, dtype=jnp.float32) -> jax.Array:
    return (jax.random.normal(key, (vocab, d)) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# linear / norm applies
# ---------------------------------------------------------------------------

def linear(x: jax.Array, w: jax.Array, mask=None) -> jax.Array:
    """y = x @ (w masked if sparse). Dense gradients via straight-through.

    Serving-representation dispatch (paper Sec. 4.4 "same weights, multiple
    representations"): the ``mask`` argument selects the execution path. The
    per-stack choice is made by repro.sparse.plan (each format's cost model
    over the request batch shape); this function only dispatches on the
    leaf's TYPE:

    * bool array — masked-dense MXU path (training / prefill default), with
      the straight-through trick so the gradient stays dense (the RigL/SRigL
      grow criterion needs it).
    * ``repro.sparse.formats.SparseFormat`` — the format executes itself
      (``fmt.apply(x, w)``): MaskedDense / Condensed / StructuredFanIn /
      CondensedOverActive, each one point of PAPER.md Fig. 4 (see the
      formats module docstring for the mapping; the structured and
      condensed-over-active points run the ablation-aware Pallas kernels of
      kernels.structured_matmul — gathered columns / fused scatter).
    * legacy dict leaf — auto-upgraded through the deprecation shim
      (``formats.from_legacy_leaf``); a dict with unrecognized keys raises a
      clear error instead of silently mis-dispatching.
    """
    if mask is None:
        return x @ w.astype(x.dtype)
    # one scope for every sparse stack, whatever its representation: a
    # profile attributes their device time by it
    with jax.named_scope("sparse"):
        if isinstance(mask, dict):
            # pre-formats serving trees: upgrade, then dispatch on type
            mask = F.from_legacy_leaf(mask, d_in=w.shape[-2],
                                      d_out=w.shape[-1])
        if isinstance(mask, F.SparseFormat):
            return mask.apply(x, w)
        w = apply_mask_for_forward(w, mask)
        return x @ w.astype(x.dtype)


def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    x = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return (x * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array, eps: float = 1e-6) -> jax.Array:
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(dt)


# ---------------------------------------------------------------------------
# RoPE
# ---------------------------------------------------------------------------

def rope_freqs(head_dim: int, theta: float) -> jax.Array:
    return 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))


def apply_rope(x: jax.Array, positions: jax.Array, theta: float) -> jax.Array:
    """x: (..., T, H, D); positions: broadcastable to (..., T)."""
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)                       # (D/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (..., T, D/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]  # (..., T, 1, D/2)
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def apply_mrope(x: jax.Array, positions: jax.Array, theta: float,
                sections=(2, 1, 1)) -> jax.Array:
    """Multimodal RoPE (Qwen2-VL): 3 position streams (t, h, w) over D/2 bands.

    x: (B, T, H, D); positions: (3, B, T). Frequency bands are split into
    sections proportional to ``sections`` and each uses its own position ids.
    """
    d = x.shape[-1]
    freqs = rope_freqs(d, theta)  # (D/2,)
    n = d // 2
    total = sum(sections)
    bounds = []
    acc = 0
    for s in sections:
        nxt = acc + (n * s) // total
        bounds.append((acc, nxt))
        acc = nxt
    bounds[-1] = (bounds[-1][0], n)
    # Select per-band position stream.
    band_pos = []
    for axis, (lo, hi) in enumerate(bounds):
        p = positions[axis]  # (B, T)
        band_pos.append(p[..., None].astype(jnp.float32) * freqs[lo:hi])
    ang = jnp.concatenate(band_pos, axis=-1)  # (B, T, D/2)
    cos, sin = jnp.cos(ang)[..., None, :], jnp.sin(ang)[..., None, :]
    x1, x2 = x[..., :n], x[..., n:]
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


def swiglu(gate: jax.Array, up: jax.Array) -> jax.Array:
    return jax.nn.silu(gate) * up
