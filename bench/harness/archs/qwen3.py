"""Qwen3 (``model_type`` "qwen3"): the dense decoder block of the published
Qwen3 description, its weights, its plain float32 forward pass and the
operations a training step of it requires.

Block: pre-norm RMSNorm, grouped-query attention with per-head RMSNorm on q
and k before rotary embedding (half-split rotation), causal softmax, SwiGLU
MLP; tied embedding and head. SRigL makes ``wo`` and the three MLP matrices
sparse (the configuration's ``sparsity.fan_in``); q, k and v stay dense.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from harness import cost, program, reference
from harness.weights import Leaf

# the program's train-step scopes this block adds to harness.scopes.COMMON
SCOPES = ("attention",)
COUNTERS = ()


def arch_config(model: dict, *, dtype: str, param_dtype: str):
    """The program's ``ArchConfig``: its dense family with qk-norm."""
    from repro.configs.base import ArchConfig
    return ArchConfig(
        name=model["name"], family="dense",
        n_layers=int(model["num_hidden_layers"]),
        d_model=model["hidden_size"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        d_ff=model["intermediate_size"], vocab_size=model["vocab_size"],
        qk_norm=True, rope_theta=float(model["rope_theta"]),
        tie_embeddings=bool(model["tie_word_embeddings"]),
        norm_eps=float(model["rms_norm_eps"]), dtype=dtype,
        param_dtype=param_dtype, sparsity=program.sparsity_config(model))


def layout(model: dict) -> dict[tuple[str, ...], Leaf]:
    """The embedding, the final norm, then the block stack over layers."""
    n, d, hd = (model["num_hidden_layers"], model["hidden_size"],
                model["head_dim"])
    qd = model["num_attention_heads"] * hd
    kvd = model["num_key_value_heads"] * hd
    ff = model["intermediate_size"]
    fan = model["sparsity"]["fan_in"]
    blocks = {"ln1": ((d,), "norm"), "ln2": ((d,), "norm"),
              "q_norm": ((hd,), "norm"), "k_norm": ((hd,), "norm"),
              "wq": ((d, qd), "dense"), "wk": ((d, kvd), "dense"),
              "wv": ((d, kvd), "dense"), "wo": ((qd, d), "sparse"),
              "w_gate": ((d, ff), "sparse"), "w_up": ((d, ff), "sparse"),
              "w_down": ((ff, d), "sparse")}
    out = {("embed",): Leaf((), (model["vocab_size"], d), "embed"),
           ("final_norm",): Leaf((), (d,), "norm")}
    for name, (shape, kind) in blocks.items():
        out["blocks", name] = Leaf((n,), shape, kind,
                                   int(fan[name]) if kind == "sparse" else 0)
    return out


# ---------------------------------------------------------------------------
# the plain reference
# ---------------------------------------------------------------------------


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    """x (T, H, D), pos (T,): rotate the two halves of each head."""
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def causal_attention(q, k, v):
    """q (T, H, D), k and v (T, Hkv, D), heads grouped over kv heads:
    (T, H * D)."""
    t, nh, hd = q.shape
    k = jnp.repeat(k, nh // k.shape[1], axis=1)
    v = jnp.repeat(v, nh // v.shape[1], axis=1)
    pos = jnp.arange(t)
    s = jnp.einsum("qhd,khd->hqk", q, k) / jnp.sqrt(jnp.float32(hd))
    s = jnp.where(pos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("hqk,khd->qhd", p, v).reshape(t, nh * hd)


def swiglu(model, w, h, quant):
    """The MLP sublayer with its residual."""
    mm = functools.partial(reference.matmul, quant=quant)
    x = rms(h, 1.0 + w["ln2"], model["rms_norm_eps"])
    return h + mm(jax.nn.silu(mm(x, w["w_gate"])) * mm(x, w["w_up"]),
                  w["w_down"])


def layer(model, lw, lm, h, quant=None):
    """One block on one sequence. h (T, d) f32; lw/lm one layer's weights
    and masks (any storage type)."""
    eps = model["rms_norm_eps"]
    nh, nkv, hd = (model["num_attention_heads"], model["num_key_value_heads"],
                   model["head_dim"])
    mm = functools.partial(reference.matmul, quant=quant)
    t = h.shape[0]
    w = reference.masked(lw, lm)
    x = rms(h, 1.0 + w["ln1"], eps)
    q = rms(mm(x, w["wq"]).reshape(t, nh, hd), 1.0 + w["q_norm"], eps)
    k = rms(mm(x, w["wk"]).reshape(t, nkv, hd), 1.0 + w["k_norm"], eps)
    v = mm(x, w["wv"]).reshape(t, nkv, hd)
    pos = jnp.arange(t)
    q, k = rope(q, pos, model["rope_theta"]), rope(k, pos, model["rope_theta"])
    h = h + mm(causal_attention(q, k, v), w["wo"])
    return swiglu(model, w, h, quant)


def hidden(model, params, masks, tokens, quant=None):
    """Final-normed hidden states (T, d) of one token sequence, each block
    under remat."""
    h = params["embed"][tokens].astype(jnp.float32)
    body = jax.checkpoint(functools.partial(layer, model, quant=quant))

    def step(h, xs):
        lw, lm = xs
        return body(lw, lm, h), None

    h, _ = jax.lax.scan(step, h, (params["blocks"], masks["blocks"]))
    return rms(h, 1.0 + params["final_norm"].astype(jnp.float32),
               model["rms_norm_eps"])


def row_loss(model, params, masks, tokens, targets, quant, chunk):
    """Summed next-token loss of one row, through the tied head."""
    hid = hidden(model, params, masks, tokens, quant)
    return reference.chunked_ce(hid, params["embed"].astype(jnp.float32).T,
                                targets, quant, chunk)


# ---------------------------------------------------------------------------
# operations
# ---------------------------------------------------------------------------


def flops_per_step(model: dict, rows: int, seq: int, counters=None) -> float:
    """Forward and backward of ``rows`` causal rows of ``seq`` tokens: the
    sparse stacks at 2 x nnz, dense q/k/v, the tied head at every token,
    attention's score and value products."""
    n, d, hd = (model["num_hidden_layers"], model["hidden_size"],
                model["head_dim"])
    nh, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    per_token = (2.0 * cost.sparse_nnz(model) + 2.0 * n * d * (nh + 2 * nkv)
                 * hd + 2.0 * d * model["vocab_size"])
    return cost.train_flops(
        rows * seq * per_token
        + cost.attention_flops(n, nh, hd, hd, cost.causal_context(rows, seq)))
