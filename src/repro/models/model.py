"""Unified decoder LM covering all assigned families.

One functional model with per-family block stacks:

  dense   — [attn + SwiGLU MLP] x L, scanned          (mistral, qwen3, internlm2,
            qwen2-vl backbone (M-RoPE), musicgen backbone (multi-codebook))
  gemma   — grouped scan: (5 local + 1 global) x G + remainder local layers,
            ring-buffer caches for local layers
  moe     — [attn + top-k MoE] x L, scanned            (granite, kimi)
  ssm     — [Mamba2/SSD mixer] x L, scanned            (mamba2-130m)
  hybrid  — groups of R Mamba2 blocks + one *shared* attention+MLP block
            applied after each group (zamba2)
  vit     — encoder-only (non-causal) [attn + MLP] x L, class head (paper arch)

All layer stacks are ``lax.scan``-ed (stacked params) so HLO size and compile
time stay O(1) in depth — essential for the 512-device dry-runs. Sparse layers
receive boolean masks (same pytree layout as the stacked weights) and use the
straight-through masked matmul from repro.models.layers.
"""
from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.models import attention as A
from repro.models import layers as L
from repro.models import moe as MOE
from repro.models import ssm as SSM

Params = dict
Masks = dict


def _mesh_ok():
    """Abstract mesh of the current trace, or None outside ``jax.set_mesh``."""
    mesh = jax.sharding.get_abstract_mesh()
    return None if mesh.empty else mesh


def shard_hint(x: jax.Array, *spec):
    """with_sharding_constraint iff tracing under a mesh with these axes and
    every constrained dim is divisible by its axis product (no-op on CPU
    tests / decode T=1 / odd shapes)."""
    mesh = _mesh_ok()
    if mesh is None:
        return x
    names = mesh.axis_names
    for dim, a in zip(x.shape, spec):
        if a is None:
            continue
        axes = a if isinstance(a, tuple) else (a,)
        n = 1
        for ax in axes:
            if ax not in names:
                return x
            n *= mesh.shape[ax]
        if n == 0 or dim % n:
            return x
    from jax.sharding import PartitionSpec as P
    return jax.lax.with_sharding_constraint(x, P(*spec))


# ---------------------------------------------------------------------------
# ZeRO-3 / Megatron-SP compute-layout hints
# ---------------------------------------------------------------------------
# When cfg.fsdp is on, weights are STORED with their non-TP dim sharded over
# 'data'. GSPMD, left alone, may resolve the (data-sharded weight x
# data-sharded batch) contraction by replicating the *batch* — catastrophic
# for activation memory (observed: kimi attention tensors at full
# global-batch). ZeRO-3 semantics require the WEIGHT to be all-gathered at
# use instead; we pin that choice by constraining each weight slab to its
# TP-only layout inside the layer scans. Masks follow their weights.

_COL_TP = {"wq": "attn", "wk": "kv", "wv": "kv", "w_gate": "ff", "w_up": "ff",
           "in_z": "ssm", "in_x": "ssm"}
_ROW_TP = {"wo": "attn", "w_down": "ff", "out_proj": "ssm"}


def _tp_ok(cfg, kind: str, tp: int) -> bool:
    return {
        "attn": cfg.n_heads_padded % tp == 0,
        "kv": cfg.n_kv_heads_padded % tp == 0,
        "ff": bool(cfg.d_ff) and cfg.d_ff % tp == 0,
        "ssm": cfg.ssm_state > 0 and cfg.ssm_n_heads % tp == 0,
    }[kind]


def gather_weights(cfg, tree: dict) -> dict:
    """Constrain weight/mask slabs to TP-only sharding (fsdp axis gathered)."""
    mesh = _mesh_ok()
    if mesh is None or "model" not in mesh.axis_names or not cfg.fsdp:
        return tree
    tp = mesh.shape["model"]
    from jax.sharding import PartitionSpec as P

    def spec_for(name, ndim):
        is_expert = cfg.is_moe and name in ("w_gate", "w_up", "w_down")
        if is_expert:  # slab (E, d, ff): E over model, rest gathered
            ep = "model" if cfg.n_experts % tp == 0 else None
            return P(*([None] * (ndim - 3) + [ep, None, None]))
        if name in _COL_TP:
            t = "model" if _tp_ok(cfg, _COL_TP[name], tp) else None
            return P(*([None] * (ndim - 2) + [None, t]))
        if name in _ROW_TP:
            t = "model" if _tp_ok(cfg, _ROW_TP[name], tp) else None
            return P(*([None] * (ndim - 2) + [t, None]))
        return None

    out = {}
    for k, v in tree.items():
        sp = spec_for(k, getattr(v, "ndim", 0)) if hasattr(v, "ndim") else None
        out[k] = jax.lax.with_sharding_constraint(v, sp) if sp is not None else v
    return out


def _any_tp(cfg) -> bool:
    """Does this arch use the 'model' axis for tensor parallelism at all?
    (pure-DP archs carry batch on 'model'; vocab hints must not steal it)"""
    mesh = _mesh_ok()
    if mesh is None or "model" not in mesh.axis_names:
        return False
    tp = mesh.shape["model"]
    kinds = any(_tp_ok(cfg, k, tp) for k in ("attn", "kv", "ff", "ssm"))
    return kinds or (cfg.is_moe and cfg.n_experts % tp == 0)


def vocab_hint(cfg, head: jax.Array, axis: int = -1) -> jax.Array:
    """Shard the LM head's vocab dim (``axis``) over 'model' (TP archs only)."""
    if not _any_tp(cfg):
        return head
    spec = [None] * head.ndim
    spec[axis] = "model"
    return shard_hint(head, *spec)


def seq_shard(cfg, x: jax.Array) -> jax.Array:
    """Megatron-SP: residual stream (B, T, d) sharded over 'model' on T at
    block boundaries — remat-saved activations shrink by the TP degree; the
    partitioner inserts the all-gather/reduce-scatter pair around attention
    and MLP (same bytes as the classic per-block all-reduces)."""
    if cfg.family in ("ssm", "hybrid"):  # SSD scans need the full sequence
        return x
    if x.ndim != 3 or x.shape[1] < 2:
        return x
    return shard_hint(x, None, "model", None)


def _dt(cfg):
    return jnp.dtype(cfg.dtype)


def _pdt(cfg):
    return jnp.dtype(cfg.param_dtype)


# ===========================================================================
# init
# ===========================================================================

def _init_attn_block(key, cfg, dtype, k_fan: dict, with_mlp: bool = True) -> dict:
    ks = jax.random.split(key, 8)
    d, qd, kvd, hd = cfg.d_model, cfg.q_dim, cfg.kv_dim, cfg.head_dim

    def maybe_sparse(k, a, b, name):
        fan = k_fan.get(name)
        return L.sparse_init(k, a, b, fan, dtype) if fan else L.dense_init(k, a, b, dtype)

    p = {
        "ln1": jnp.zeros((d,), dtype),
        "wq": maybe_sparse(ks[0], d, qd, "wq"),
        "wk": maybe_sparse(ks[1], d, kvd, "wk"),
        "wv": maybe_sparse(ks[2], d, kvd, "wv"),
        "wo": maybe_sparse(ks[3], qd, d, "wo"),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dtype)
        p["k_norm"] = jnp.zeros((hd,), dtype)
    if with_mlp:
        p["ln2"] = jnp.zeros((d,), dtype)
        p["w_gate"] = maybe_sparse(ks[4], d, cfg.d_ff, "w_gate")
        p["w_up"] = maybe_sparse(ks[5], d, cfg.d_ff, "w_up")
        p["w_down"] = maybe_sparse(ks[6], cfg.d_ff, d, "w_down")
    return p


def _init_moe_block(key, cfg, dtype, k_fan: dict) -> dict:
    k1, k2 = jax.random.split(key)
    p = _init_attn_block(k1, cfg, dtype, k_fan, with_mlp=False)
    p["ln2"] = jnp.zeros((cfg.d_model,), dtype)
    moe = MOE.init_moe_params(k2, cfg.d_model, cfg.d_ff, cfg.n_experts,
                              {k: v for k, v in k_fan.items() if v}, dtype)
    p.update(moe._asdict())
    return p


def _init_ssm_block(key, cfg, dtype, k_fan: dict) -> dict:
    p = SSM.init_ssm_params(key, cfg, dtype, k_fan)._asdict()
    p["ln"] = jnp.zeros((cfg.d_model,), dtype)
    return p


def _stack(init_fn, key, n: int):
    """Initialize ``n`` blocks with independent keys, stacked on axis 0."""
    return jax.vmap(init_fn)(jax.random.split(key, n))


def init_params(cfg, key: jax.Array, k_fan: dict | None = None) -> Params:
    """Initialize the full parameter pytree for ``cfg``.

    ``k_fan`` maps sparse layer names (wq/wo/w_gate/... ) to their constant
    fan-in k so sparse layers get 1/sqrt(k)-scaled init (Evci et al. 2022);
    produced by repro.sparse.registry.
    """
    k_fan = k_fan or {}
    dtype = _pdt(cfg)
    keys = jax.random.split(key, 8)
    d, v = cfg.d_model, cfg.vocab_size
    params: Params = {"final_norm": jnp.zeros((d,), dtype)}

    # --- embeddings / heads -------------------------------------------------
    vp = cfg.vocab_padded
    if cfg.family == "audio":
        params["embed"] = jax.vmap(lambda k: L.embed_init(k, vp, d, dtype))(
            jax.random.split(keys[0], cfg.n_codebooks))
        params["lm_head"] = jax.vmap(lambda k: L.dense_init(k, d, vp, dtype))(
            jax.random.split(keys[1], cfg.n_codebooks))
    elif cfg.family == "vit":
        params["embed"] = L.embed_init(keys[0], 1, d, dtype)  # CLS token
        params["lm_head"] = L.dense_init(keys[1], d, cfg.n_classes, dtype)
    else:
        params["embed"] = L.embed_init(keys[0], vp, d, dtype)
        if not cfg.tie_embeddings:
            params["lm_head"] = L.dense_init(keys[1], d, vp, dtype)

    # --- block stacks -------------------------------------------------------
    if cfg.family in ("dense", "vlm", "audio", "vit"):
        if cfg.local_global_ratio:  # gemma3 grouped layout
            r = cfg.local_global_ratio
            n_groups = cfg.n_layers // (r + 1)
            rem = cfg.n_layers - n_groups * (r + 1)
            init = lambda k: _init_attn_block(k, cfg, dtype, k_fan)
            params["g_local"] = jax.vmap(lambda ks: jax.vmap(init)(ks))(
                jax.random.split(keys[2], n_groups * r).reshape(n_groups, r, 2))
            params["g_global"] = _stack(init, keys[3], n_groups)
            if rem:
                params["g_rem"] = _stack(init, keys[4], rem)
        else:
            params["blocks"] = _stack(
                lambda k: _init_attn_block(k, cfg, dtype, k_fan), keys[2], cfg.n_layers)
    elif cfg.family == "moe":
        params["blocks"] = _stack(
            lambda k: _init_moe_block(k, cfg, dtype, k_fan), keys[2], cfg.n_layers)
    elif cfg.family == "ssm":
        params["blocks"] = _stack(
            lambda k: _init_ssm_block(k, cfg, dtype, k_fan), keys[2], cfg.n_layers)
    elif cfg.family == "hybrid":
        r = cfg.hybrid_attn_every
        n_groups = cfg.n_layers // r
        rem = cfg.n_layers - n_groups * r
        init = lambda k: _init_ssm_block(k, cfg, dtype, k_fan)
        params["m_groups"] = jax.vmap(lambda ks: jax.vmap(init)(ks))(
            jax.random.split(keys[2], n_groups * r).reshape(n_groups, r, 2))
        if rem:
            params["m_rem"] = _stack(init, keys[4], rem)
        params["shared_attn"] = _init_attn_block(keys[3], cfg, dtype, k_fan)
    else:
        raise ValueError(cfg.family)
    return params


# ===========================================================================
# sublayer applies
# ===========================================================================

def _heads(x, n, hd):
    return x.reshape(*x.shape[:-1], n, hd)


@jax.named_scope("attention")
def attn_sublayer(cfg, p: dict, m: dict, x: jax.Array, *,
                  positions, window: int, q_offset: int = 0,
                  cache: tuple | None = None, decode: bool = False,
                  paged: tuple | None = None):
    """Pre-norm attention sublayer (residual added by caller).

    cache: (k_cache, v_cache, cache_len) for decode / prefill-write.
    paged: (k_pool, v_pool, block_table, lengths) — one layer's paged KV
    pool slice instead of a contiguous cache (``supports_paged`` families
    only; window must be 0). Prefill writes positions [0, T) through the
    table; decode writes one token per stream at its own length.
    Returns (out, new_cache_kv or None).
    """
    h = L.rms_norm(x, p["ln1"], cfg.norm_eps)
    q = _heads(L.linear(h, p["wq"], m.get("wq")), cfg.n_heads_padded, cfg.head_dim)
    k = _heads(L.linear(h, p["wk"], m.get("wk")), cfg.n_kv_heads_padded, cfg.head_dim)
    v = _heads(L.linear(h, p["wv"], m.get("wv")), cfg.n_kv_heads_padded, cfg.head_dim)

    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = L.rms_norm(k, p["k_norm"], cfg.norm_eps)

    if cfg.mrope:
        q = L.apply_mrope(q, positions, cfg.rope_theta)
        k = L.apply_mrope(k, positions, cfg.rope_theta)
    elif cfg.causal:  # ViT uses learned-free identity positions
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)

    new_cache = None
    if paged is not None:
        k_pool, v_pool, block_table, lengths = paged
        if decode:
            t = k.shape[1]
            if t == 1:
                k_pool, v_pool = A.paged_cache_write(
                    k_pool, v_pool, k, v, block_table, lengths[:, None])
                attn = A.paged_decode_attention(q, k_pool, v_pool,
                                                block_table, lengths + 1,
                                                head_to_kv=cfg.head_to_kv)
            else:
                # speculative verify: T consecutive tokens per stream, token
                # i written at slot lengths[b] + i, each query attending its
                # own causal prefix (one batched dispatch instead of T)
                pos = lengths[:, None] + jnp.arange(t)[None]
                k_pool, v_pool = A.paged_cache_write(
                    k_pool, v_pool, k, v, block_table, pos)
                attn = A.paged_verify_attention(q, k_pool, v_pool,
                                                block_table, lengths,
                                                head_to_kv=cfg.head_to_kv)
        else:
            # prefill: attention over the in-flight k/v (chunked, causal —
            # right-padded rows' pads sit after every real token, so real
            # rows never attend them); the pool write covers all T slots,
            # pad slots hold garbage until decode overwrites them and are
            # masked by ``lengths`` meanwhile
            attn = A.chunked_attention(
                q, k, v, head_to_kv=cfg.head_to_kv, causal=cfg.causal,
                window=window, q_offset=q_offset, q_chunk=cfg.attn_q_chunk,
                kv_chunk=cfg.attn_kv_chunk)
            t = k.shape[1]
            pos = jnp.broadcast_to(jnp.arange(t)[None], (k.shape[0], t))
            k_pool, v_pool = A.paged_cache_write(k_pool, v_pool, k, v,
                                                 block_table, pos)
        new_cache = (k_pool, v_pool)
    elif decode:
        k_cache, v_cache, cache_len = cache
        k_cache, v_cache = A.cache_write(k_cache, v_cache, k, v, cache_len)
        attn = A.decode_attention(q, k_cache, v_cache, cache_len + 1,
                                  head_to_kv=cfg.head_to_kv, window=window)
        new_cache = (k_cache, v_cache)
    else:
        attn = A.chunked_attention(
            q, k, v, head_to_kv=cfg.head_to_kv, causal=cfg.causal, window=window,
            q_offset=q_offset, q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk)
        if cache is not None:  # prefill: fill the cache
            k_cache, v_cache, cache_len = cache
            k_cache, v_cache = A.cache_write(k_cache, v_cache, k, v, cache_len)
            new_cache = (k_cache, v_cache)

    if cfg.n_heads_padded != cfg.n_heads:  # zero padded heads (bit-exactness)
        head_mask = (jnp.arange(cfg.n_heads_padded) < cfg.n_heads)
        attn = attn * head_mask[None, None, :, None].astype(attn.dtype)
    out = L.linear(attn.reshape(*x.shape[:-1], cfg.q_dim), p["wo"], m.get("wo"))
    return out, new_cache


def mlp_sublayer(cfg, p: dict, m: dict, x: jax.Array):
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    gate = L.linear(h, p["w_gate"], m.get("w_gate"))
    up = L.linear(h, p["w_up"], m.get("w_up"))
    return L.linear(L.swiglu(gate, up), p["w_down"], m.get("w_down"))


def moe_sublayer(cfg, p: dict, m: dict, x: jax.Array):
    h = L.rms_norm(x, p["ln2"], cfg.norm_eps)
    moe_p = MOE.MoEParams(router=p["router"], w_gate=p["w_gate"],
                          w_up=p["w_up"], w_down=p["w_down"])
    y, aux = MOE.moe_block(cfg, moe_p, h, m, group_size=cfg.moe_group_size)
    return y, aux


def ssm_sublayer(cfg, p: dict, m: dict, x: jax.Array, *,
                 state=None, decode: bool = False):
    h = L.rms_norm(x, p["ln"], cfg.norm_eps)
    sp = SSM.SSMParams(**{f: p[f] for f in SSM.SSMParams._fields})
    y, new_state = SSM.ssm_block(cfg, sp, h, m, state=state,
                                 chunk=cfg.ssd_chunk, decode=decode)
    return y, new_state


# ===========================================================================
# full blocks (residual wiring) — used by the scans below
# ===========================================================================

def attn_mlp_block(cfg, p, m, x, *, positions, window, q_offset=0,
                   cache=None, decode=False, paged=None):
    p, m = gather_weights(cfg, p), gather_weights(cfg, m)
    a, new_cache = attn_sublayer(cfg, p, m, x, positions=positions, window=window,
                                 q_offset=q_offset, cache=cache, decode=decode,
                                 paged=paged)
    x = x + a
    x = x + mlp_sublayer(cfg, p, m, x)
    return seq_shard(cfg, x), new_cache


def attn_moe_block(cfg, p, m, x, *, positions, window, q_offset=0,
                   cache=None, decode=False, paged=None):
    p, m = gather_weights(cfg, p), gather_weights(cfg, m)
    a, new_cache = attn_sublayer(cfg, p, m, x, positions=positions, window=window,
                                 q_offset=q_offset, cache=cache, decode=decode,
                                 paged=paged)
    x = x + a
    y, aux = moe_sublayer(cfg, p, m, x)
    return seq_shard(cfg, x + y), new_cache, aux


def ssm_res_block(cfg, p, m, x, *, state=None, decode=False):
    p, m = gather_weights(cfg, p), gather_weights(cfg, m)
    y, new_state = ssm_sublayer(cfg, p, m, x, state=state, decode=decode)
    return x + y, new_state


# ===========================================================================
# forward (training / scoring): returns final hidden states
# ===========================================================================

def _maybe_remat(cfg, fn):
    return jax.checkpoint(fn) if cfg.remat == "block" else fn


def backbone(cfg, params: Params, masks: Masks, x: jax.Array, *,
             positions) -> tuple[jax.Array, jax.Array]:
    """Run the block stacks. x: (B, T, d). Returns (hidden, aux_loss)."""
    with jax.named_scope("blocks"):
        x, aux_total = _scan_blocks(cfg, params, masks or {}, x, positions)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return x, aux_total


def _scan_blocks(cfg, params: Params, masks: Masks, x: jax.Array,
                 positions) -> tuple[jax.Array, jax.Array]:
    """The layer scans of each family. Returns (x, aux_loss)."""
    aux_total = jnp.zeros((), jnp.float32)

    if cfg.family in ("dense", "vlm", "audio", "vit") and not cfg.local_global_ratio:
        mstack = masks.get("blocks", {})

        def body(carry, xs):
            h = carry
            p_i, m_i = xs
            h, _ = _maybe_remat(cfg, functools.partial(
                attn_mlp_block, cfg, positions=positions,
                window=cfg.sliding_window))(p_i, m_i, h)
            return h, None

        x, _ = jax.lax.scan(body, x, (params["blocks"], _expand_masks(mstack, cfg.n_layers)))

    elif cfg.local_global_ratio:  # gemma3

        def group_body(carry, xs):
            h = carry
            pl_g, ml_g, pg_g, mg_g = xs

            def local_body(hh, ys):
                p_i, m_i = ys
                hh, _ = _maybe_remat(cfg, functools.partial(
                    attn_mlp_block, cfg, positions=positions,
                    window=cfg.sliding_window))(p_i, m_i, hh)
                return hh, None

            h, _ = jax.lax.scan(local_body, h, (pl_g, ml_g))
            h, _ = _maybe_remat(cfg, functools.partial(
                attn_mlp_block, cfg, positions=positions, window=0))(pg_g, mg_g, h)
            return h, None

        x, _ = jax.lax.scan(
            group_body, x,
            (params["g_local"], _expand_masks(masks.get("g_local", {}), None),
             params["g_global"], _expand_masks(masks.get("g_global", {}), None)))
        if "g_rem" in params:
            def rem_body(carry, xs):
                p_i, m_i = xs
                h, _ = _maybe_remat(cfg, functools.partial(
                    attn_mlp_block, cfg, positions=positions,
                    window=cfg.sliding_window))(p_i, m_i, carry)
                return h, None
            x, _ = jax.lax.scan(rem_body, x,
                                (params["g_rem"], _expand_masks(masks.get("g_rem", {}), None)))

    elif cfg.family == "moe":
        def body(carry, xs):
            h, aux = carry
            p_i, m_i = xs
            h, _, a = _maybe_remat(cfg, functools.partial(
                attn_moe_block, cfg, positions=positions,
                window=cfg.sliding_window))(p_i, m_i, h)
            return (h, aux + a), None

        (x, aux_total), _ = jax.lax.scan(
            body, (x, aux_total),
            (params["blocks"], _expand_masks(masks.get("blocks", {}), cfg.n_layers)))

    elif cfg.family == "ssm":
        def body(carry, xs):
            p_i, m_i = xs
            h, _ = _maybe_remat(cfg, functools.partial(ssm_res_block, cfg))(p_i, m_i, carry)
            return h, None

        x, _ = jax.lax.scan(body, x,
                            (params["blocks"], _expand_masks(masks.get("blocks", {}), cfg.n_layers)))

    elif cfg.family == "hybrid":
        sh_p = params["shared_attn"]
        sh_m = masks.get("shared_attn", {})

        def group_body(carry, xs):
            h = carry
            p_g, m_g = xs

            def mamba_body(hh, ys):
                p_i, m_i = ys
                hh, _ = _maybe_remat(cfg, functools.partial(ssm_res_block, cfg))(p_i, m_i, hh)
                return hh, None

            h, _ = jax.lax.scan(mamba_body, h, (p_g, m_g))
            h, _ = _maybe_remat(cfg, functools.partial(
                attn_mlp_block, cfg, positions=positions,
                window=cfg.sliding_window))(sh_p, sh_m, h)
            return h, None

        x, _ = jax.lax.scan(group_body, x,
                            (params["m_groups"], _expand_masks(masks.get("m_groups", {}), None)))
        if "m_rem" in params:
            def rem_body(carry, xs):
                p_i, m_i = xs
                h, _ = _maybe_remat(cfg, functools.partial(ssm_res_block, cfg))(p_i, m_i, carry)
                return h, None
            x, _ = jax.lax.scan(rem_body, x,
                                (params["m_rem"], _expand_masks(masks.get("m_rem", {}), None)))
    else:
        raise ValueError(cfg.family)
    return x, aux_total


def _expand_masks(mstack: dict, n_layers):
    """Masks pytree for scan xs — an empty dict scans fine (no leaves)."""
    return mstack


# ===========================================================================
# embedding / loss heads
# ===========================================================================

@jax.named_scope("embed")
def embed_inputs(cfg, params: Params, batch: dict) -> tuple[jax.Array, Any]:
    """Token/frontend embedding. Returns (x (B,T,d), positions)."""
    dt = _dt(cfg)
    if cfg.family == "audio":
        # tokens: (B, K, T) — sum codebook embeddings (EnCodec frontend stub)
        toks = batch["tokens"]
        x = sum(params["embed"][k][toks[:, k]] for k in range(cfg.n_codebooks))
        bsz, t = toks.shape[0], toks.shape[2]
    elif cfg.family == "vit":
        x = batch["frontend_embeds"]  # precomputed patch embeddings (stub)
        bsz, t = x.shape[0], x.shape[1]
    else:
        toks = batch["tokens"]
        x = params["embed"][toks]
        if "frontend_embeds" in batch:  # VLM: add precomputed patch embeds
            x = x + batch["frontend_embeds"].astype(x.dtype)
        bsz, t = toks.shape
    x = x.astype(dt)

    if cfg.mrope:
        positions = batch.get("mrope_positions")
        if positions is None:
            p = jnp.broadcast_to(jnp.arange(t)[None], (bsz, t))
            positions = jnp.stack([p, p, p])
    else:
        positions = batch.get("positions")
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(t)[None], (bsz, t))
    return x, positions


def cross_entropy_chunked(hidden: jax.Array, lm_head: jax.Array,
                          targets: jax.Array, chunk: int,
                          loss_mask: jax.Array | None = None,
                          valid_vocab: int = 0, cfg=None, *,
                          vocab_rows: bool = False) -> jax.Array:
    """Mean token CE without materializing (B, T, V) logits.

    hidden: (B, T, d); lm_head: (d, V), or with ``vocab_rows`` a (V, d)
    table (tied embeddings), contracted over d as it is stored so that
    neither the table nor its gradient is transposed; targets: (B, T) int32.
    Scans over T chunks; each chunk computes (B, Tc, V) logits. Under
    differentiation the same sweep also forms the gradient (``_ce_sweep``).
    """
    b, t, d = hidden.shape
    chunk = min(chunk, t)
    nc = -(-t // chunk)
    pad = nc * chunk - t
    if pad:
        hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, pad)))
        lm = jnp.pad(loss_mask, ((0, 0), (0, pad))) if loss_mask is not None \
            else jnp.pad(jnp.ones((b, t), jnp.float32), ((0, 0), (0, pad)))
    else:
        lm = loss_mask if loss_mask is not None else jnp.ones((b, t), jnp.float32)

    hc = hidden.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    tc = targets.reshape(b, nc, chunk).transpose(1, 0, 2)
    mc = lm.reshape(b, nc, chunk).transpose(1, 0, 2).astype(jnp.float32)

    # vocab-shard the head over the TP axis (tied embeddings arrive d-sharded;
    # without this the per-chunk logits would be replicated over 'model' and
    # the partial-sum all-reduce costs chunks x B x Tc x V f32 — the single
    # largest collective in the naive lowering)
    v_axis = 0 if vocab_rows else 1
    v_total = lm_head.shape[v_axis]
    if cfg is not None:
        lm_head = vocab_hint(cfg, lm_head, axis=v_axis)
    n_valid = valid_vocab if valid_vocab else v_total
    return _ce_sweep(n_valid, vocab_rows, hc, lm_head, tc, mc)


def _ce_pass(n_valid: int, vocab_rows: bool, hc, lm_head, tc, mc, *,
             with_grad: bool):
    """One scan over the chunks: the mean NLL and, ``with_grad``, its
    gradient in (hc, lm_head) at unit cotangent.

    The loss is the last op of the forward pass and a scalar, so each
    chunk's dlogits = (softmax - onehot) * mask / count is known as soon as
    its logits are: dh and dW come from the same logits, with no chunk
    kept or recomputed for a backward pass. Matmuls take the head in the
    compute dtype (cast once) and accumulate in f32; logits are that
    matmul's output, dlogits the compute dtype, dW sums in f32."""
    w = lm_head.astype(hc.dtype)
    v_axis = 0 if vocab_rows else 1
    cols = jnp.arange(w.shape[v_axis])
    cnt = jnp.maximum(jnp.sum(mc), 1.0)

    def step(carry, xs):
        tot, dw = carry
        h_i, t_i, m_i = xs
        logits = jax.lax.dot_general(
            h_i, w, (((2,), (1 - v_axis,)), ((), ()))).astype(jnp.float32)
        if n_valid != cols.shape[0]:  # mask padded vocab columns
            logits = jnp.where(cols < n_valid, logits, -1e30)
        lse = jax.nn.logsumexp(logits, axis=-1)
        hit = cols == t_i[..., None]
        gold = jnp.sum(jnp.where(hit, logits, 0.0), axis=-1)
        tot = tot + jnp.sum((lse - gold) * m_i)
        if not with_grad:
            return (tot, dw), None
        dl = ((jnp.exp(logits - lse[..., None]) - hit)
              * (m_i / cnt)[..., None]).astype(h_i.dtype)
        dh = jax.lax.dot_general(dl, w, (((2,), (v_axis,)), ((), ())))
        # dW in the head's own layout: (V, d) for vocab rows, else (d, V)
        a, c = (dl, h_i) if vocab_rows else (h_i, dl)
        dw = dw + jax.lax.dot_general(a, c, (((0, 1), (0, 1)), ((), ())),
                                      preferred_element_type=jnp.float32)
        return (tot, dw), dh

    dw0 = jnp.zeros(w.shape, jnp.float32) if with_grad else None
    (tot, dw), dh = jax.lax.scan(step, (jnp.zeros(()), dw0), (hc, tc, mc))
    return tot / cnt, dh, dw


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _ce_sweep(n_valid, vocab_rows, hc, lm_head, tc, mc):
    """Mean NLL over chunked (nc, B, Tc, d) hidden states; called without
    differentiation it runs the loss alone."""
    return _ce_pass(n_valid, vocab_rows, hc, lm_head, tc, mc,
                    with_grad=False)[0]


def _ce_sweep_fwd(n_valid, vocab_rows, hc, lm_head, tc, mc):
    loss, dh, dw = _ce_pass(n_valid, vocab_rows, hc, lm_head, tc, mc,
                            with_grad=True)
    return loss, (dh, dw.astype(lm_head.dtype))


def _ce_sweep_bwd(n_valid, vocab_rows, res, g):
    # the gradient is already whole; scale it by the incoming cotangent
    dh, dw = res
    return ((dh * g).astype(dh.dtype), (dw * g).astype(dw.dtype), None, None)


_ce_sweep.defvjp(_ce_sweep_fwd, _ce_sweep_bwd)


def loss_fn(cfg, params: Params, masks: Masks, batch: dict) -> tuple[jax.Array, dict]:
    """Training loss (next-token CE, or classification CE for ViT)."""
    x, positions = embed_inputs(cfg, params, batch)
    hidden, aux = backbone(cfg, params, masks, x, positions=positions)

    with jax.named_scope("head"):
        if cfg.family == "vit":
            pooled = jnp.mean(hidden, axis=1)
            logits = (pooled @ params["lm_head"].astype(pooled.dtype)).astype(jnp.float32)
            labels = batch["labels"]
            loss = jnp.mean(jax.nn.logsumexp(logits, -1)
                            - jnp.take_along_axis(logits, labels[:, None], -1)[:, 0])
        elif cfg.family == "audio":
            losses = [
                cross_entropy_chunked(hidden, params["lm_head"][k],
                                      batch["targets"][:, k], cfg.ce_chunk,
                                      valid_vocab=cfg.vocab_size, cfg=cfg)
                for k in range(cfg.n_codebooks)
            ]
            loss = sum(losses) / cfg.n_codebooks
        else:
            tied = cfg.tie_embeddings
            head = params["embed"] if tied else params["lm_head"]
            loss = cross_entropy_chunked(hidden, head, batch["targets"], cfg.ce_chunk,
                                         batch.get("loss_mask"),
                                         valid_vocab=cfg.vocab_size, cfg=cfg,
                                         vocab_rows=tied)

    total = loss + 0.01 * aux
    return total, {"loss": loss, "aux_loss": aux}


# ===========================================================================
# serving: KV / SSM caches + single-token decode
# ===========================================================================

def _attn_cache(cfg, n: int, bsz: int, s: int, dtype):
    hkv, hd = cfg.n_kv_heads_padded, cfg.head_dim
    shape = (n, bsz, s, hkv, hd) if n else (bsz, s, hkv, hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _ssm_cache(cfg, n: int, bsz: int, dtype):
    w = cfg.ssm_conv_width - 1
    lead = (n,) if n else ()
    return {
        "conv_x": jnp.zeros((*lead, bsz, w, cfg.d_inner), dtype),
        "conv_bc": jnp.zeros((*lead, bsz, w, 2 * cfg.ssm_state), dtype),
        "h": jnp.zeros((*lead, bsz, cfg.ssm_n_heads, cfg.ssm_head_dim, cfg.ssm_state),
                       jnp.float32),
    }


def init_cache(cfg, bsz: int, max_len: int) -> dict:
    """Decode-state pytree for a batch of ``bsz`` streams of up to ``max_len``.

    Windowed (local) attention layers get ring buffers of size ``window``
    instead of ``max_len`` — for gemma3's 5:1 local:global pattern this cuts
    long-context cache memory by ~5x (the 500k cell relies on it).
    """
    dt = _dt(cfg)
    cache: dict = {"len": jnp.zeros((), jnp.int32)}
    if cfg.family in ("dense", "vlm", "audio") and not cfg.local_global_ratio:
        s = min(cfg.sliding_window, max_len) if cfg.sliding_window else max_len
        cache["blocks"] = _attn_cache(cfg, cfg.n_layers, bsz, s, dt)
    elif cfg.local_global_ratio:
        r = cfg.local_global_ratio
        g = cfg.n_layers // (r + 1)
        rem = cfg.n_layers - g * (r + 1)
        w = min(cfg.sliding_window, max_len)
        loc = _attn_cache(cfg, g * r, bsz, w, dt)
        cache["g_local"] = jax.tree.map(lambda a: a.reshape(g, r, *a.shape[1:]), loc)
        cache["g_global"] = _attn_cache(cfg, g, bsz, max_len, dt)
        if rem:
            cache["g_rem"] = _attn_cache(cfg, rem, bsz, w, dt)
    elif cfg.family == "moe":
        cache["blocks"] = _attn_cache(cfg, cfg.n_layers, bsz, max_len, dt)
    elif cfg.family == "ssm":
        cache["blocks"] = _ssm_cache(cfg, cfg.n_layers, bsz, dt)
    elif cfg.family == "hybrid":
        r = cfg.hybrid_attn_every
        g = cfg.n_layers // r
        rem = cfg.n_layers - g * r
        mg = _ssm_cache(cfg, g * r, bsz, dt)
        cache["m_groups"] = jax.tree.map(lambda a: a.reshape(g, r, *a.shape[1:]), mg)
        if rem:
            cache["m_rem"] = _ssm_cache(cfg, rem, bsz, dt)
        cache["shared_attn"] = _attn_cache(cfg, g, bsz, max_len, dt)
    return cache


# ---------------------------------------------------------------------------
# paged serving (continuous batching): shared page pool + per-stream tables
# ---------------------------------------------------------------------------

def supports_paged(cfg) -> bool:
    """Can this arch decode against a paged KV pool?

    The paged read/write path covers the uniform full-attention stacks
    (dense/vlm/moe "blocks" layouts). Windowed ring buffers, gemma's
    local/global grouping, M-RoPE position triples, multi-codebook audio
    and SSM state are served by the legacy contiguous-cache path.
    """
    return (cfg.family in ("dense", "vlm", "moe")
            and cfg.causal
            and not cfg.local_global_ratio
            and not cfg.sliding_window
            and not cfg.mrope)


def init_paged_pool(cfg, num_blocks: int, block_size: int) -> dict:
    """Layer-stacked page pool: {"pk"/"pv": (L, P, bs, Hkv, D)}.

    Page 0 is reserved as the garbage page (see repro.models.paged) —
    allocators must never hand it out.
    """
    dt = _dt(cfg)
    shape = (cfg.n_layers, num_blocks, block_size,
             cfg.n_kv_heads_padded, cfg.head_dim)
    return {"pk": jnp.zeros(shape, dt), "pv": jnp.zeros(shape, dt)}


def _paged_attn_scan(cfg, x, params, masks, pool, block_table, lengths,
                     positions, decode: bool):
    """Scan the attention(+mlp/moe) stack with per-layer pool slices as
    scan xs/ys (same structure the contiguous k/v caches use)."""
    has_moe = cfg.family == "moe"

    def body(carry, xs):
        h = carry
        p_i, m_i, kp, vp = xs
        pg = (kp, vp, block_table, lengths)
        if has_moe:
            h, (nk, nv), _aux = attn_moe_block(
                cfg, p_i, m_i, h, positions=positions, window=0,
                paged=pg, decode=decode)
        else:
            h, (nk, nv) = attn_mlp_block(
                cfg, p_i, m_i, h, positions=positions, window=0,
                paged=pg, decode=decode)
        return h, (nk, nv)

    x, (nk, nv) = jax.lax.scan(
        body, x, (params["blocks"], masks.get("blocks", {}),
                  pool["pk"], pool["pv"]))
    return x, {"pk": nk, "pv": nv}


def _lm_logits(cfg, params, last: jax.Array) -> jax.Array:
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    head = vocab_hint(cfg, head)
    logits = (last @ head.astype(last.dtype)).astype(jnp.float32)
    if cfg.vocab_padded != cfg.vocab_size:
        logits = jnp.where(jnp.arange(cfg.vocab_padded) < cfg.vocab_size,
                           logits, -jnp.inf)
    return logits


def paged_prefill_step(cfg, params: Params, masks: Masks, batch: dict,
                       pool: dict, block_table: jax.Array,
                       prompt_lens: jax.Array):
    """Prefill right-padded prompts into a paged KV pool.

    batch["tokens"]: (B, T) right-padded to the prompt bucket;
    prompt_lens: (B,) real lengths (0 for idle rows, whose all-zero table
    rows point at the reserved garbage page). Causal chunked attention means
    real tokens never attend a pad; each row's logits are read at its OWN
    last real token, so results are bitwise those of an unpadded prefill.
    Returns (logits (B, V), new pool).
    """
    masks = masks or {}
    x, positions = embed_inputs(cfg, params, batch)
    x, new_pool = _paged_attn_scan(cfg, x, params, masks, pool, block_table,
                                   prompt_lens, positions, decode=False)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    last = x[jnp.arange(x.shape[0]), jnp.maximum(prompt_lens - 1, 0)]
    return _lm_logits(cfg, params, last), new_pool


def paged_decode_step(cfg, params: Params, masks: Masks, batch: dict,
                      pool: dict, block_table: jax.Array, lengths: jax.Array):
    """One-token decode against the paged pool, per-stream positions.

    batch["tokens"]: (B, 1); lengths: (B,) tokens already present per
    stream (the new token is written at slot ``lengths[b]`` and attends
    ``lengths[b] + 1`` slots — exactly the contiguous decode_step math with
    the scalar cache length replaced by a vector). Returns (logits, pool).
    """
    masks = masks or {}
    x, positions = embed_inputs(cfg, params, batch)
    positions = positions + lengths[:, None]
    x, new_pool = _paged_attn_scan(cfg, x, params, masks, pool, block_table,
                                   lengths, positions, decode=True)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _lm_logits(cfg, params, x[:, 0]), new_pool


def paged_verify_step(cfg, params: Params, masks: Masks, batch: dict,
                      pool: dict, block_table: jax.Array, lengths: jax.Array):
    """Multi-position decode (speculative verification).

    batch["tokens"]: (B, T) — token ``i`` is written at slot
    ``lengths[b] + i`` and attends ``lengths[b] + i + 1`` slots, exactly
    the visibility of T sequential ``paged_decode_step`` calls, collapsed
    into ONE full-network dispatch. Returns (logits (B, T, V), pool);
    ``argmax(logits[:, i])`` is the model's next token after consuming
    ``batch["tokens"][:, :i + 1]`` — what a sequential greedy decode would
    emit at that position.
    """
    masks = masks or {}
    x, positions = embed_inputs(cfg, params, batch)
    positions = positions + lengths[:, None]
    x, new_pool = _paged_attn_scan(cfg, x, params, masks, pool, block_table,
                                   lengths, positions, decode=True)
    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    return _lm_logits(cfg, params, x), new_pool


def _decode_attn_scan(cfg, stack_p, stack_m, kc, vc, x, positions, window, cache_len):
    """Scan attention(+mlp/moe) layers for one decode step, updating caches."""
    has_moe = cfg.family == "moe"

    def body(carry, xs):
        h = carry
        p_i, m_i, k_i, v_i = xs
        if has_moe:
            h, (nk, nv), _aux = attn_moe_block(
                cfg, p_i, m_i, h, positions=positions, window=window,
                cache=(k_i, v_i, cache_len), decode=True)
        else:
            h, (nk, nv) = attn_mlp_block(
                cfg, p_i, m_i, h, positions=positions, window=window,
                cache=(k_i, v_i, cache_len), decode=True)
        return h, (nk, nv)

    x, (nk, nv) = jax.lax.scan(body, x, (stack_p, stack_m, kc, vc))
    return x, nk, nv


def _decode_ssm_scan(cfg, stack_p, stack_m, st, x):
    def body(carry, xs):
        p_i, m_i, s_i = xs
        h, ns = ssm_res_block(cfg, p_i, m_i, carry,
                              state=(s_i["conv_x"], s_i["conv_bc"], s_i["h"]),
                              decode=True)
        return h, {"conv_x": ns[0], "conv_bc": ns[1], "h": ns[2]}

    x, new_st = jax.lax.scan(body, x, (stack_p, stack_m, st))
    return x, new_st


def prefill_step(cfg, params: Params, masks: Masks, batch: dict, cache: dict):
    """Process a full prompt, fill the decode caches, return last-token logits.

    batch["tokens"]: (B, T) (audio: (B, K, T)). Returns (logits, cache).
    """
    masks = masks or {}
    x, positions = embed_inputs(cfg, params, batch)
    pos0 = cache["len"]
    t = x.shape[1]
    new_cache: dict = {"len": pos0 + t}

    def attn_scan(stack_p, stack_m, kc, vc, h, window):
        has_moe = cfg.family == "moe"

        def body(carry, xs):
            hh = carry
            p_i, m_i, k_i, v_i = xs
            if has_moe:
                hh, (nk, nv), _aux = attn_moe_block(
                    cfg, p_i, m_i, hh, positions=positions, window=window,
                    cache=(k_i, v_i, pos0), decode=False)
            else:
                hh, (nk, nv) = attn_mlp_block(
                    cfg, p_i, m_i, hh, positions=positions, window=window,
                    cache=(k_i, v_i, pos0), decode=False)
            return hh, (nk, nv)

        h, (nk, nv) = jax.lax.scan(body, h, (stack_p, stack_m, kc, vc))
        return h, nk, nv

    def ssm_scan(stack_p, stack_m, st, h):
        def body(carry, xs):
            p_i, m_i, s_i = xs
            hh, ns = ssm_res_block(cfg, p_i, m_i, carry,
                                   state=(s_i["conv_x"], s_i["conv_bc"], s_i["h"]),
                                   decode=False)
            return hh, {"conv_x": ns[0], "conv_bc": ns[1], "h": ns[2]}

        h, new_st = jax.lax.scan(body, h, (stack_p, stack_m, st))
        return h, new_st

    if cfg.family in ("dense", "vlm", "audio", "moe") and not cfg.local_global_ratio:
        c = cache["blocks"]
        x, nk, nv = attn_scan(params["blocks"], masks.get("blocks", {}),
                              c["k"], c["v"], x, cfg.sliding_window)
        new_cache["blocks"] = {"k": nk, "v": nv}
    elif cfg.local_global_ratio:
        w = cfg.sliding_window

        def group_body(carry, xs):
            h = carry
            pl, ml, kcl, vcl, pg, mg, kcg, vcg = xs
            h, nkl, nvl = attn_scan(pl, ml, kcl, vcl, h, w)
            h, (nkg, nvg) = attn_mlp_block(cfg, pg, mg, h, positions=positions,
                                           window=0, cache=(kcg, vcg, pos0),
                                           decode=False)
            return h, (nkl, nvl, nkg, nvg)

        cl, cg = cache["g_local"], cache["g_global"]
        x, (nkl, nvl, nkg, nvg) = jax.lax.scan(
            group_body, x,
            (params["g_local"], masks.get("g_local", {}), cl["k"], cl["v"],
             params["g_global"], masks.get("g_global", {}), cg["k"], cg["v"]))
        new_cache["g_local"] = {"k": nkl, "v": nvl}
        new_cache["g_global"] = {"k": nkg, "v": nvg}
        if "g_rem" in params:
            cr = cache["g_rem"]
            x, nk, nv = attn_scan(params["g_rem"], masks.get("g_rem", {}),
                                  cr["k"], cr["v"], x, w)
            new_cache["g_rem"] = {"k": nk, "v": nv}
    elif cfg.family == "ssm":
        x, new_st = ssm_scan(params["blocks"], masks.get("blocks", {}),
                             cache["blocks"], x)
        new_cache["blocks"] = new_st
    elif cfg.family == "hybrid":
        sh_p, sh_m = params["shared_attn"], masks.get("shared_attn", {})
        ca = cache["shared_attn"]

        def group_body(carry, xs):
            h = carry
            p_g, m_g, st_g, ka, va = xs
            h, new_st = ssm_scan(p_g, m_g, st_g, h)
            h, (nka, nva) = attn_mlp_block(cfg, sh_p, sh_m, h, positions=positions,
                                           window=0, cache=(ka, va, pos0),
                                           decode=False)
            return h, (new_st, nka, nva)

        x, (new_st, nka, nva) = jax.lax.scan(
            group_body, x,
            (params["m_groups"], masks.get("m_groups", {}), cache["m_groups"],
             ca["k"], ca["v"]))
        new_cache["m_groups"] = new_st
        new_cache["shared_attn"] = {"k": nka, "v": nva}
        if "m_rem" in params:
            x, new_rem = ssm_scan(params["m_rem"], masks.get("m_rem", {}),
                                  cache["m_rem"], x)
            new_cache["m_rem"] = new_rem
    else:
        raise ValueError(cfg.family)

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    last = x[:, -1]
    if cfg.family == "audio":
        logits = jnp.stack(
            [(last @ vocab_hint(cfg, params["lm_head"][k]).astype(x.dtype)
              ).astype(jnp.float32) for k in range(cfg.n_codebooks)], axis=1)
    else:
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        head = vocab_hint(cfg, head)
        logits = (last @ head.astype(x.dtype)).astype(jnp.float32)
    if cfg.vocab_padded != cfg.vocab_size:
        logits = jnp.where(jnp.arange(cfg.vocab_padded) < cfg.vocab_size,
                           logits, -jnp.inf)
    return logits, new_cache


def decode_step(cfg, params: Params, masks: Masks, batch: dict, cache: dict):
    """One-token decode. batch["tokens"]: (B, 1) (audio: (B, K, 1)).

    Returns (logits (B, V) [audio: (B, K, V)], new_cache).
    """
    masks = masks or {}
    x, positions = embed_inputs(cfg, params, batch)
    pos = cache["len"]
    if cfg.mrope:
        positions = positions + pos  # all three streams advance in time
    else:
        positions = positions + pos
    new_cache: dict = {"len": pos + 1}

    if cfg.family in ("dense", "vlm", "audio", "moe") and not cfg.local_global_ratio:
        c = cache["blocks"]
        x, nk, nv = _decode_attn_scan(
            cfg, params["blocks"], masks.get("blocks", {}), c["k"], c["v"], x,
            positions, cfg.sliding_window, pos)
        new_cache["blocks"] = {"k": nk, "v": nv}

    elif cfg.local_global_ratio:  # gemma3
        w = cfg.sliding_window

        def group_body(carry, xs):
            h = carry
            pl, ml, kcl, vcl, pg, mg, kcg, vcg = xs
            h, nkl, nvl = _decode_attn_scan(cfg, pl, ml, kcl, vcl, h, positions, w, pos)
            h, (nkg, nvg) = attn_mlp_block(cfg, pg, mg, h, positions=positions,
                                           window=0, cache=(kcg, vcg, pos), decode=True)
            return h, (nkl, nvl, nkg, nvg)

        cl, cg = cache["g_local"], cache["g_global"]
        x, (nkl, nvl, nkg, nvg) = jax.lax.scan(
            group_body, x,
            (params["g_local"], masks.get("g_local", {}), cl["k"], cl["v"],
             params["g_global"], masks.get("g_global", {}), cg["k"], cg["v"]))
        new_cache["g_local"] = {"k": nkl, "v": nvl}
        new_cache["g_global"] = {"k": nkg, "v": nvg}
        if "g_rem" in params:
            cr = cache["g_rem"]
            x, nk, nv = _decode_attn_scan(
                cfg, params["g_rem"], masks.get("g_rem", {}), cr["k"], cr["v"], x,
                positions, w, pos)
            new_cache["g_rem"] = {"k": nk, "v": nv}

    elif cfg.family == "ssm":
        x, new_st = _decode_ssm_scan(cfg, params["blocks"], masks.get("blocks", {}),
                                     cache["blocks"], x)
        new_cache["blocks"] = new_st

    elif cfg.family == "hybrid":
        sh_p, sh_m = params["shared_attn"], masks.get("shared_attn", {})
        ca = cache["shared_attn"]

        def group_body(carry, xs):
            h = carry
            p_g, m_g, st_g, ka, va = xs
            h, new_st = _decode_ssm_scan(cfg, p_g, m_g, st_g, h)
            h, (nka, nva) = attn_mlp_block(cfg, sh_p, sh_m, h, positions=positions,
                                           window=0, cache=(ka, va, pos), decode=True)
            return h, (new_st, nka, nva)

        x, (new_st, nka, nva) = jax.lax.scan(
            group_body, x,
            (params["m_groups"], masks.get("m_groups", {}), cache["m_groups"],
             ca["k"], ca["v"]))
        new_cache["m_groups"] = new_st
        new_cache["shared_attn"] = {"k": nka, "v": nva}
        if "m_rem" in params:
            x, new_rem = _decode_ssm_scan(cfg, params["m_rem"], masks.get("m_rem", {}),
                                          cache["m_rem"], x)
            new_cache["m_rem"] = new_rem
    else:
        raise ValueError(cfg.family)

    x = L.rms_norm(x, params["final_norm"], cfg.norm_eps)
    if cfg.family == "audio":
        logits = jnp.stack(
            [(x[:, 0] @ vocab_hint(cfg, params["lm_head"][k]).astype(x.dtype)
              ).astype(jnp.float32)
             for k in range(cfg.n_codebooks)], axis=1)
    else:
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        head = vocab_hint(cfg, head)
        logits = (x[:, 0] @ head.astype(x.dtype)).astype(jnp.float32)
    if cfg.vocab_padded != cfg.vocab_size:  # mask padded vocab columns
        logits = jnp.where(jnp.arange(cfg.vocab_padded) < cfg.vocab_size,
                           logits, -jnp.inf)
    return logits, new_cache
