"""GQA attention: chunked (flash-style) prefill/train + KV-cache decode.

Design points (see DESIGN.md §4):

* **Chunked attention**: queries are processed in *statically unrolled* chunks;
  each q-chunk attends only to the kv prefix it can causally see (exact static
  slice), with an inner ``lax.scan`` over kv chunks carrying online-softmax
  stats. No O(T^2) score tensor is ever live, and — unlike a masked full scan —
  no FLOPs are spent above the diagonal at the chunk level.
* **GQA via gather-expand**: kv heads are expanded to the query-head axis with
  a static ``head_to_kv`` gather. Under TP the q-head axis is sharded and kv is
  replicated (GQA kv counts rarely divide the TP degree), so the gather is
  shard-local and each device materializes only its own heads' kv — the
  standard Megatron/MaxText GQA-TP layout. When head counts don't divide the
  TP degree they are padded (configs.base.ArchConfig.pad_heads_to) and a
  ``head_mask`` zeroes padded heads' outputs, keeping results bit-exact.
* **Sliding window**: windowed layers slice a static ``(q_chunk + window)`` kv
  slab per q-chunk → O(T·window) compute, and use a **ring-buffer KV cache** of
  size ``window`` at decode time (gemma3's 5:1 local:global pattern makes the
  500k-context cell affordable: only the rare global layers keep full caches).
* **Fused flash path**: on a TPU, causal full-context attention whose
  sequence is a whole number of kernel blocks and whose head size fills the
  lanes runs as one Pallas flash kernel (``flash_attention``, the shipped
  splash kernel): the score tile stays in VMEM, tiles above the diagonal
  are skipped, and the backward pass is the kernel's own dq/dkv kernels.
  Everything else takes the chunked scan; ``path_counts`` says which ran.
"""
from __future__ import annotations

import collections

import jax
import jax.numpy as jnp
from jax.experimental.pallas.ops.tpu.splash_attention import (
    splash_attention_kernel as splash, splash_attention_mask as splash_mask)

NEG_INF = jnp.float32(-1e30)

# Trace-time count of the path each chunked_attention call took.
_PATH_COUNTS = collections.Counter()

# Flash q and kv block sizes, largest first; a sequence must divide by the
# last. Scores are computed FLASH_KV_COMPUTE keys at a time within a block,
# and a block's q or kv tile holds at most FLASH_TILE_BYTES (larger ones
# overflow VMEM in the dq kernel on a v5e). Tuned on a v5e at T 2048, D 128
# (benchmarks/attention_blocks.py).
FLASH_BLOCKS = (1024, 512, 256, 128)
FLASH_KV_COMPUTE = 512
FLASH_TILE_BYTES = 512 * 1024


def path_counts() -> dict[str, int]:
    """How many ``chunked_attention`` calls traced so far took the fused
    flash kernel (``flash``) and the chunked scan (``chunked``)."""
    return {"flash": _PATH_COUNTS["flash"], "chunked": _PATH_COUNTS["chunked"]}


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _kv_group(h: int, head_to_kv: tuple) -> int:
    """Q heads per kv head when ``head_to_kv`` groups them contiguously (q
    head i reads kv head i // g), the kernel's own grouping; else 0."""
    g = h // max(max(head_to_kv, default=0) + 1, 1)
    return g if g and head_to_kv == tuple(i // g for i in range(h)) else 0


def _one_device(x) -> bool:
    """Whether ``x`` lives in a one-device program: XLA cannot partition a
    Mosaic kernel over a mesh (tensor-parallel serving) by itself."""
    mesh = jax.typeof(x).sharding.mesh
    return mesh.empty or mesh.size == 1


def flash_qualifies(q, k, *, head_to_kv, causal, window, q_offset) -> bool:
    """Whether the fused flash kernel computes this call: on one TPU,
    causal attention over the whole context, a sequence of whole kernel
    blocks, lane-wide heads and contiguous GQA groups."""
    _, tq, h, d = q.shape
    return (_on_tpu() and causal and window == 0 and q_offset == 0
            and tq == k.shape[1] and tq % FLASH_BLOCKS[-1] == 0
            and d % 128 == 0 and _kv_group(h, head_to_kv) * k.shape[2] == h
            and _one_device(q))


def flash_block_sizes(t: int, d: int, itemsize: int) -> splash.BlockSizes:
    """The kernel's tiles for a sequence of ``t`` and heads of ``d`` with
    ``itemsize`` bytes per element: q and kv blocks of the largest of
    ``FLASH_BLOCKS`` that divides ``t`` and keeps a tile within
    ``FLASH_TILE_BYTES``, for every phase. The backward pass keeps separate
    dq and dkv kernels: the fused one sums per-block dq partials rounded to
    the input dtype."""
    b = next(b for b in FLASH_BLOCKS if t % b == 0 and (
        b * d * itemsize <= FLASH_TILE_BYTES or b == FLASH_BLOCKS[-1]))
    c = min(b, FLASH_KV_COMPUTE)
    return splash.BlockSizes(block_q=b, block_kv=b, block_kv_compute=c,
                             block_q_dkv=b, block_kv_dkv=b,
                             block_kv_dkv_compute=c, block_q_dq=b,
                             block_kv_dq=b)


def flash_attention(q, k, v, *, block_sizes: splash.BlockSizes | None = None,
                    interpret: bool = False) -> jax.Array:
    """Causal attention over the whole context as one Pallas flash kernel.

    q: (B, T, H, D); k, v: (B, T, Hkv, D) with q head i reading kv head
    i // (H / Hkv). Returns (B, T, H, D). q is pre-scaled by D ** -0.5 in
    its own dtype, as ``chunked_attention`` does; scores and the softmax
    statistics are f32. Differentiable: the backward pass runs the
    kernel's dq and dkv kernels. ``block_sizes`` defaults to
    ``flash_block_sizes``.
    """
    b, t, h, d = q.shape
    mask = splash_mask.MultiHeadMask([splash_mask.CausalMask((t, t))] * h)
    kernel = splash.make_splash_mha(
        mask, block_sizes=block_sizes or flash_block_sizes(
            t, d, q.dtype.itemsize), head_shards=1,
        q_seq_shards=1, interpret=interpret)
    heads_first = lambda x: x.transpose(0, 2, 1, 3)  # noqa: E731
    out = jax.vmap(kernel)(heads_first(q * d ** -0.5), heads_first(k),
                           heads_first(v))
    return heads_first(out).astype(v.dtype)


def expand_kv(k: jax.Array, head_to_kv: tuple) -> jax.Array:
    """(B, S, Hkv, D) -> (B, S, H, D) by the static q-head -> kv-head map.

    Identity maps (MHA) are returned untouched (no gather in the HLO).
    """
    if head_to_kv == tuple(range(k.shape[2])):
        return k
    idx = jnp.asarray(head_to_kv, jnp.int32)
    return jnp.take(k, idx, axis=2)


def chunked_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    head_to_kv: tuple,
    causal: bool = True,
    window: int = 0,
    q_offset: int = 0,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
) -> jax.Array:
    """Memory-efficient attention.

    q: (B, Tq, H, D); k, v: (B, S, Hkv, D). Returns (B, Tq, H, D).
    ``q_offset`` is the absolute position of q[0] (for prefill continuation).
    Takes the fused flash kernel where ``flash_qualifies``.
    """
    if flash_qualifies(q, k, head_to_kv=head_to_kv, causal=causal,
                       window=window, q_offset=q_offset):
        _PATH_COUNTS["flash"] += 1
        return flash_attention(q, k, v)
    _PATH_COUNTS["chunked"] += 1
    b, tq, h, d = q.shape
    s = k.shape[1]
    scale = d ** -0.5
    q = q * scale
    k = expand_kv(k, head_to_kv)
    v = expand_kv(v, head_to_kv)

    q_chunk = min(q_chunk, tq)
    n_q = -(-tq // q_chunk)
    pad_q = n_q * q_chunk - tq
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))

    outs = []
    for i in range(n_q):  # static unroll: exact causal kv extent per chunk
        q_i = q[:, i * q_chunk: (i + 1) * q_chunk]
        q_lo = q_offset + i * q_chunk
        q_hi = q_lo + q_chunk
        kv_hi = min(s, q_hi) if causal else s
        kv_lo = max(0, q_lo - window + 1) if (window and causal) else 0
        kv_lo = (kv_lo // kv_chunk) * kv_chunk
        kv_hi = min(s, -(-kv_hi // kv_chunk) * kv_chunk)
        if kv_hi <= kv_lo:  # fully masked chunk (can happen with offsets)
            outs.append(jnp.zeros((b, q_chunk, h, d), v.dtype))
            continue
        outs.append(
            _attend_one_q_chunk(
                q_i, k[:, kv_lo:kv_hi], v[:, kv_lo:kv_hi],
                q_pos0=q_lo, kv_pos0=kv_lo, causal=causal,
                window=window, kv_chunk=kv_chunk,
            )
        )
    out = jnp.concatenate(outs, axis=1)[:, :tq]
    return out


def _attend_one_q_chunk(q_i, k_i, v_i, *, q_pos0, kv_pos0, causal, window, kv_chunk):
    """Online-softmax scan over kv chunks for one q chunk.

    q_i: (B, Qc, H, D); k_i/v_i: (B, Skv, H, D) — the causal slab, kv expanded.
    """
    b, qc, h, d = q_i.shape
    skv = k_i.shape[1]
    kv_chunk = min(kv_chunk, skv)
    n_kv = -(-skv // kv_chunk)
    pad = n_kv * kv_chunk - skv
    if pad:
        k_i = jnp.pad(k_i, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_i = jnp.pad(v_i, ((0, 0), (0, pad), (0, 0), (0, 0)))

    k_c = k_i.reshape(b, n_kv, kv_chunk, h, d).transpose(1, 0, 2, 3, 4)
    v_c = v_i.reshape(b, n_kv, kv_chunk, h, d).transpose(1, 0, 2, 3, 4)

    q_pos = q_pos0 + jnp.arange(qc)

    def step(carry, xs):
        acc, m, l = carry
        k_blk, v_blk, blk_idx = xs
        kv_pos = kv_pos0 + blk_idx * kv_chunk + jnp.arange(kv_chunk)
        s_blk = jnp.einsum("bqhd,bshd->bhqs", q_i, k_blk,
                           preferred_element_type=jnp.float32)
        mask = jnp.ones((qc, kv_chunk), bool)
        if causal:
            mask &= kv_pos[None, :] <= q_pos[:, None]
        if window:
            mask &= kv_pos[None, :] > q_pos[:, None] - window
        mask &= kv_pos[None, :] < kv_pos0 + skv  # padded kv tail
        s_blk = jnp.where(mask[None, None], s_blk, NEG_INF)
        m_new = jnp.maximum(m, jnp.max(s_blk, axis=-1))
        p = jnp.exp(s_blk - m_new[..., None])
        corr = jnp.exp(m - m_new)
        l_new = l * corr + jnp.sum(p, axis=-1)
        upd = jnp.einsum("bhqs,bshd->bhqd", p.astype(v_blk.dtype), v_blk)
        acc_new = acc * corr[..., None].astype(acc.dtype) + upd.astype(jnp.float32)
        return (acc_new, m_new, l_new), None

    acc0 = jnp.zeros((b, h, qc, d), jnp.float32)
    m0 = jnp.full((b, h, qc), NEG_INF)
    l0 = jnp.zeros((b, h, qc), jnp.float32)
    (acc, m, l), _ = jax.lax.scan(step, (acc0, m0, l0), (k_c, v_c, jnp.arange(n_kv)))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 2, 1, 3).astype(v_i.dtype)  # (B, Qc, H, D)


# ---------------------------------------------------------------------------
# decode (KV cache)
# ---------------------------------------------------------------------------

def decode_attention(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    cache_len: jax.Array,
    *,
    head_to_kv: tuple,
    window: int = 0,
) -> jax.Array:
    """Single-token attention against a (possibly ring-buffered) KV cache.

    q: (B, 1, H, D); k_cache/v_cache: (B, S, Hkv, D); cache_len: () int32 —
    total tokens *including* the one just written. For windowed layers
    S == window and slot j holds the most recent absolute position
    t < cache_len with t % S == j.
    """
    b, _, h, d = q.shape
    s = k_cache.shape[1]
    scale = d ** -0.5

    k_exp = expand_kv(k_cache, head_to_kv)
    v_exp = expand_kv(v_cache, head_to_kv)
    scores = jnp.einsum("bqhd,bshd->bhqs", q * scale, k_exp,
                        preferred_element_type=jnp.float32)[:, :, 0]  # (B, H, S)

    slots = jnp.arange(s)
    if window:
        # absolute position held by each ring slot
        t = cache_len - 1 - ((cache_len - 1 - slots) % s)
        valid = (t >= 0) & (t < cache_len) & (t > cache_len - 1 - window)
    else:
        valid = slots < cache_len
    scores = jnp.where(valid[None, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", p.astype(v_exp.dtype), v_exp)
    return out[:, None].transpose(0, 1, 2, 3).reshape(b, 1, h, d)


def paged_decode_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_table: jax.Array,
    lengths: jax.Array,
    *,
    head_to_kv: tuple,
) -> jax.Array:
    """Single-token attention against a paged KV pool with per-stream lengths.

    q: (B, 1, H, D); k_pool/v_pool: (P, bs, Hkv, D) — one layer's page pool;
    block_table: (B, NB) int32 page ids in position order; lengths: (B,)
    int32 tokens per stream *including* the one just written. Token ``t`` of
    stream ``b`` lives at ``(block_table[b, t // bs], t % bs)``.

    Slots at or beyond a stream's length are masked to ``NEG_INF`` before
    the softmax, so their weights underflow to exact 0.0 — results are
    bitwise independent of whatever garbage the masked pages hold (pad rows
    point their whole table at the reserved page 0). This is the same
    exact-zero argument ``chunked_attention`` uses for its kv-tail padding.
    """
    b, _, h, d = q.shape
    nb = block_table.shape[1]
    bs = k_pool.shape[1]
    scale = d ** -0.5

    # gather each stream's pages; position order is the table's entry order
    k = k_pool[block_table].reshape(b, nb * bs, *k_pool.shape[2:])
    v = v_pool[block_table].reshape(b, nb * bs, *v_pool.shape[2:])
    k_exp = expand_kv(k, head_to_kv)
    v_exp = expand_kv(v, head_to_kv)
    scores = jnp.einsum("bqhd,bshd->bhqs", q * scale, k_exp,
                        preferred_element_type=jnp.float32)[:, :, 0]  # (B, H, S)

    valid = jnp.arange(nb * bs)[None, :] < lengths[:, None]          # (B, S)
    scores = jnp.where(valid[:, None, :], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", p.astype(v_exp.dtype), v_exp)
    return out[:, None]


def paged_verify_attention(
    q: jax.Array,
    k_pool: jax.Array,
    v_pool: jax.Array,
    block_table: jax.Array,
    lengths: jax.Array,
    *,
    head_to_kv: tuple,
) -> jax.Array:
    """Multi-position attention against a paged KV pool (speculative verify).

    q: (B, T, H, D) — T consecutive tokens per stream, token ``i`` sitting
    at absolute slot ``lengths[b] + i`` (already written to the pool);
    lengths: (B,) tokens committed per stream BEFORE this dispatch. Query
    ``i`` attends slots ``< lengths[b] + i + 1`` — exactly the visibility a
    sequential chain of ``paged_decode_attention`` calls would give it, so
    one batched dispatch scores every drafted position. Masked slots hit
    ``NEG_INF`` before the softmax (exact-zero weights), so results are
    bitwise independent of garbage beyond each query's own prefix.
    """
    b, t, h, d = q.shape
    nb = block_table.shape[1]
    bs = k_pool.shape[1]
    scale = d ** -0.5

    k = k_pool[block_table].reshape(b, nb * bs, *k_pool.shape[2:])
    v = v_pool[block_table].reshape(b, nb * bs, *v_pool.shape[2:])
    k_exp = expand_kv(k, head_to_kv)
    v_exp = expand_kv(v, head_to_kv)
    scores = jnp.einsum("bqhd,bshd->bhqs", q * scale, k_exp,
                        preferred_element_type=jnp.float32)    # (B, H, T, S)

    visible = lengths[:, None] + 1 + jnp.arange(t)[None]               # (B, T)
    valid = jnp.arange(nb * bs)[None, None, :] < visible[:, :, None]   # (B, T, S)
    scores = jnp.where(valid[:, None], scores, NEG_INF)
    p = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqs,bshd->bqhd", p.astype(v_exp.dtype), v_exp)


def paged_cache_write(k_pool, v_pool, k_new, v_new, block_table, positions):
    """Scatter T new tokens per stream into a paged pool.

    k_pool/v_pool: (P, bs, Hkv, D); k_new/v_new: (B, T, Hkv, D);
    block_table: (B, NB) int32; positions: (B, T) int32 absolute token slots.
    Positions past a stream's table extent clamp into its last table entry —
    idle rows keep an all-zero table, so overshooting writes land in the
    reserved garbage page 0 and never touch a live stream's pages.
    """
    bs = k_pool.shape[1]
    nb = block_table.shape[1]
    page = jnp.minimum(positions // bs, nb - 1)                       # (B, T)
    blk = jnp.take_along_axis(block_table, page, axis=1)              # (B, T)
    off = positions % bs
    k_pool = k_pool.at[blk, off].set(k_new.astype(k_pool.dtype))
    v_pool = v_pool.at[blk, off].set(v_new.astype(v_pool.dtype))
    return k_pool, v_pool


def cache_write(k_cache, v_cache, k_new, v_new, cache_len):
    """Write T_new tokens into the cache (ring semantics if cache is smaller).

    k_cache: (B, S, Hkv, D); k_new: (B, T, Hkv, D); cache_len: tokens already
    present. Returns updated caches.
    """
    s = k_cache.shape[1]
    t = k_new.shape[1]
    if t >= s:  # only the trailing window survives a big prefill
        k_new, v_new = k_new[:, -s:], v_new[:, -s:]
        off = t - s
        pos = (cache_len + off + jnp.arange(s)) % s
    else:
        pos = (cache_len + jnp.arange(t)) % s
    k_cache = k_cache.at[:, pos].set(k_new.astype(k_cache.dtype))
    v_cache = v_cache.at[:, pos].set(v_new.astype(v_cache.dtype))
    return k_cache, v_cache
