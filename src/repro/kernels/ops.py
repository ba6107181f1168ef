"""Jit'd public wrappers for the sparse serving kernels, with custom VJPs.

``condensed_linear`` is the layer-level op used by repro.sparse.condensed:
forward runs the Pallas kernel; the backward pass computes

  dx = scatter-add of dy * values   (jnp; XLA lowers this well on TPU)
  dw = Pallas dw kernel (gather formulation, batch-tiled, no scatter needed)

``structured_linear`` is the layer-level op behind the StructuredFanIn
format (column-gathered Pallas matmul + fused scatter epilogue from
kernels.structured_matmul), and ``condensed_over_active_linear_nd`` runs the
FUSED condensed-over-active kernel (output written through out_index inside
the kernel — no standalone scatter dispatch on the decode path).

All three are inference-first (decode / online serving); training uses the
masked-dense MXU path (repro.sparse.masked), so the jnp backward pieces here
are not on the training hot path.

Block-shape resolution (when the caller does not force one): the tuned
winner from repro.sparse.autotune's persistent cache for this backend +
shape + batch bucket (keys derive from ``formats.shape_tuning_key`` — the
structured kernel's keys carry ``kind="structured"``), else the untimed
VMEM-budget default inside the kernel module (which also routes B <= 8 to
the decode-specialized variants). ``interpret`` resolves from the backend —
interpret mode on the CPU only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import condensed_matmul as cm
from repro.kernels import ref
from repro.kernels import structured_matmul as sm


def _resolve_blocks(batch: int, d_in: int, n_out: int, k: int,
                    block_b, block_n, itemsize: int, kind: str = "condensed",
                    scatter_width: int | None = None,
                    values_dtype: str | None = None):
    """Caller-forced blocks win; else the autotune cache; else (None, None)
    so the kernel module applies its VMEM-budget default.

    The cache key is derived through the format protocol
    (``formats.shape_tuning_key`` — the same derivation the formats'
    ``tuning_key`` methods and ``autotune.tune_registry`` use, so a tuned
    entry written under a format's key is exactly what this dispatch reads
    back). ``kind``/``scatter_width`` select the ablation-aware kernels' key
    spaces ("structured" and "coa" entries are timed on THOSE kernels, whose
    VMEM geometry includes the dense scatter width — see
    ``formats.shape_tuning_key``). The cache is consulted only when NEITHER
    dim is forced: a tuned winner was validated as a PAIR, so splicing one
    of its dims against an arbitrary caller-forced other dim could exceed
    the VMEM budget — with a half-forced call the remaining dim goes to the
    kernel module's budget fit instead. ``values_dtype`` (quantized storage:
    "int8"/"fp8") selects the quantized key space — quantized shapes are
    timed on the dequant-fused kernels, whose balance differs."""
    if block_b is not None or block_n is not None:
        return block_b, block_n
    # lazy imports: keep kernels importable alone
    from repro.sparse import autotune
    from repro.sparse import formats
    tuned = autotune.lookup_entry(
        formats.shape_tuning_key(d_in, n_out, k, batch, itemsize=itemsize,
                                 kind=kind, scatter_width=scatter_width,
                                 values_dtype=values_dtype))
    if tuned is not None:
        return tuned["block_b"], tuned["block_n"]
    return None, None


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def condensed_linear(
    x: jax.Array,
    values: jax.Array,
    indices: jax.Array,
    block_b: int | None = None,
    block_n: int | None = None,
) -> jax.Array:
    """y[b, n] = sum_k x[b, indices[n, k]] * values[n, k]."""
    bb, bn = _resolve_blocks(x.shape[0], x.shape[-1], *values.shape,
                             block_b, block_n, jnp.dtype(x.dtype).itemsize)
    return cm.condensed_matmul(x, values, indices, block_b=bb, block_n=bn)


def _fwd(x, values, indices, block_b, block_n):
    y = condensed_linear(x, values, indices, block_b, block_n)
    return y, (x, values, indices)


def _bwd(block_b, block_n, res, dy):
    x, values, indices = res
    dx = ref.condensed_matmul_dx_ref(dy, values, indices, x.shape[-1]).astype(x.dtype)
    dw = cm.condensed_matmul_dw(dy, x, indices, block_n=block_n)
    return dx, dw.astype(values.dtype), None


condensed_linear.defvjp(_fwd, _bwd)


def _quantized_name(values: jax.Array) -> str:
    """Tuning-key tag for quantized storage ("int8" / "fp8")."""
    from repro.sparse import formats
    return formats.resolve_quantize_spec(values.dtype)


def condensed_linear_nd(x: jax.Array, values: jax.Array, indices: jax.Array,
                        *, scales: jax.Array | None = None, **kw) -> jax.Array:
    """Rank-polymorphic wrapper: flattens leading dims to the batch axis.

    ``scales`` marks ``values`` as int8/fp8 codes and routes to the
    dequant-fused kernel (inference-only: no custom VJP — quantized storage
    is a serving artifact, training runs the masked path)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if scales is None:
        y = condensed_linear(x2, values, indices, **kw)
        return y.reshape(*lead, values.shape[0])
    bb, bn = _resolve_blocks(x2.shape[0], x2.shape[-1], *values.shape,
                             kw.get("block_b"), kw.get("block_n"),
                             jnp.dtype(x.dtype).itemsize,
                             values_dtype=_quantized_name(values))
    y = cm.condensed_matmul(x2, values, indices, scales=scales,
                            block_b=bb, block_n=bn)
    return y.reshape(*lead, values.shape[0])


def _dy_active(dy, out_index, d_out: int):
    """Gather dy at the surviving rows' dense positions; padding rows
    (out_index == d_out) get exact-zero cotangents — the drop semantics of
    the fused scatter epilogue."""
    sel = jnp.take(dy, jnp.minimum(out_index, d_out - 1), axis=1)
    return sel * (out_index < d_out)[None, :].astype(sel.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5, 6))
def condensed_over_active_linear(
    x: jax.Array,
    values: jax.Array,
    indices: jax.Array,
    out_index: jax.Array,
    d_out: int,
    block_b: int | None = None,
    block_n: int | None = None,
) -> jax.Array:
    """Fused composed Fig. 4 representation: condensed gather over ACTIVE
    rows, output written through out_index inside the kernel.

    values/indices: (a, k) condensed arrays covering only surviving (non-
    ablated) neurons; out_index: (a,) int32 position of each surviving row in
    the full (d_out,) output, with out-of-range entries (== d_out) marking
    padding rows. The kernel runs over a <= d_out rows — the ablated-neuron
    fraction converts directly into fewer HBM bytes AND fewer gather FLOPs —
    and its fused epilogue scatters each row into the dense output layout
    in-kernel (ablated neurons are exact zeros, so greedy decode stays
    token-identical to the masked path). Unlike the previous compose-then-
    scatter lowering there is no standalone ``y.at[:, out_index].add``
    dispatch and no compact-activation HBM round trip per layer.
    """
    a, k = values.shape
    bb, bn = _resolve_blocks(x.shape[0], x.shape[-1], a, k, block_b, block_n,
                             jnp.dtype(x.dtype).itemsize, kind="coa",
                             scatter_width=d_out)
    return sm.condensed_over_active_matmul(x, values, indices, out_index,
                                           d_out, block_b=bb, block_n=bn)


def _coa_fwd(x, values, indices, out_index, d_out, block_b, block_n):
    y = condensed_over_active_linear(x, values, indices, out_index, d_out,
                                     block_b, block_n)
    return y, (x, values, indices, out_index)


def _coa_bwd(d_out, block_b, block_n, res, dy):
    x, values, indices, out_index = res
    dy_act = _dy_active(dy, out_index, d_out)                # (B, a)
    dx = ref.condensed_matmul_dx_ref(dy_act, values, indices,
                                     x.shape[-1]).astype(x.dtype)
    dw = cm.condensed_matmul_dw(dy_act, x, indices, block_n=block_n)
    return dx, dw.astype(values.dtype), None, None


condensed_over_active_linear.defvjp(_coa_fwd, _coa_bwd)


def condensed_over_active_linear_nd(x: jax.Array, values: jax.Array,
                                    indices: jax.Array, out_index: jax.Array,
                                    d_out: int, *,
                                    scales: jax.Array | None = None,
                                    **kw) -> jax.Array:
    """Rank-polymorphic wrapper over the FUSED condensed-over-active kernel
    (flattens leading dims to the batch axis). ``scales`` routes to the
    dequant-fused quantized kernel (inference-only, no custom VJP)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    if scales is None:
        y = condensed_over_active_linear(x2, values, indices, out_index,
                                         d_out, **kw)
        return y.reshape(*lead, d_out)
    bb, bn = _resolve_blocks(x2.shape[0], x2.shape[-1], *values.shape,
                             kw.get("block_b"), kw.get("block_n"),
                             jnp.dtype(x.dtype).itemsize, kind="coa",
                             scatter_width=d_out,
                             values_dtype=_quantized_name(values))
    y = sm.condensed_over_active_matmul(x2, values, indices, out_index,
                                        d_out, scales=scales,
                                        block_b=bb, block_n=bn)
    return y.reshape(*lead, d_out)


def condensed_over_active_linear_nd_unfused(
        x: jax.Array, values: jax.Array, indices: jax.Array,
        out_index: jax.Array, d_out: int, **kw) -> jax.Array:
    """Pre-fusion composition (reference): condensed gather over active rows,
    then a separate XLA scatter into the dense layout. Kept as the oracle the
    fused kernel is tested against, and as the lowering whose standalone
    scatter dispatch the fused epilogue provably removes (see the HLO
    dispatch-count test)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    y_act = condensed_linear(x2, values, indices, **kw)      # (B, a)
    y = jnp.zeros((x2.shape[0], d_out), y_act.dtype)
    # active rows are unique, padding rows point at d_out -> dropped
    y = y.at[:, out_index].add(y_act, mode="drop")
    return y.reshape(*lead, d_out)


def structured_dense(x: jax.Array, weight: jax.Array, neuron_active: jax.Array) -> jax.Array:
    """Reference "structured-only" path from Fig. 4: drop ablated neurons,
    dense matmul.

    weight: (d_in, n_out); computes x @ weight with ablated outputs forced to
    exact zeros. This is the pure-jnp ORACLE the column-gathered Pallas
    kernel (``structured_linear`` / kernels.structured_matmul) is validated
    against — bit-identical on every active set, including zero ablation,
    all-but-one-ablated, non-tile-aligned active counts and bf16. It reads
    the full dense weight (the formulation the hot path executed before the
    gathered kernel landed); serving dispatches go through
    ``structured_linear``, whose HBM weight bytes and MXU FLOPs scale with
    the active fraction, and whose cost is what
    ``formats.StructuredFanIn.estimate_cost`` prices.
    """
    w = weight * neuron_active[None, :].astype(weight.dtype)
    return x @ w


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def structured_linear(
    x: jax.Array,
    w: jax.Array,
    active_index: jax.Array,
    block_b: int | None = None,
    block_n: int | None = None,
) -> jax.Array:
    """Column-gathered structured matmul: y = x @ w over the surviving
    columns only, ablated outputs exact zeros (fused scatter epilogue).

    ``active_index``: (a_pad,) int32 surviving-column ids, padded to tile
    alignment with the out-of-range sentinel ``d_out`` (see
    ``formats.StructuredFanIn`` / ``structured_matmul.padded_active_count``).
    Exact (bit-identical) to ``structured_dense`` with the matching
    neuron_active bools, for ablation-only masks the exact serving path.
    """
    d_out = w.shape[-1]
    bb, bn = _resolve_blocks(x.shape[0], x.shape[-1], active_index.shape[0],
                             0, block_b, block_n,
                             jnp.dtype(x.dtype).itemsize, kind="structured",
                             scatter_width=d_out)
    return sm.structured_matmul(x, w.astype(x.dtype), active_index,
                                block_b=bb, block_n=bn)


def _structured_fwd(x, w, active_index, block_b, block_n):
    y = structured_linear(x, w, active_index, block_b, block_n)
    return y, (x, w, active_index)


def _structured_bwd(block_b, block_n, res, dy):
    x, w, active_index = res
    d_out = w.shape[-1]
    dy_act = _dy_active(dy, active_index, d_out)             # (B, a_pad)
    w_act = sm._gather_columns(w, active_index).astype(dy_act.dtype)
    dx = (dy_act @ w_act.T).astype(x.dtype)
    # dw: only surviving columns receive gradient (ablated columns are
    # dropped from the forward); padding entries scatter out of range
    contrib = (x.astype(dy_act.dtype).T @ dy_act)            # (d_in, a_pad)
    dw = jnp.zeros_like(w).at[:, active_index].add(
        contrib.astype(w.dtype), mode="drop")
    return dx, dw, None


structured_linear.defvjp(_structured_fwd, _structured_bwd)


def structured_linear_nd(x: jax.Array, w: jax.Array,
                         active_index: jax.Array, **kw) -> jax.Array:
    """Rank-polymorphic wrapper: flattens leading dims to the batch axis."""
    lead = x.shape[:-1]
    y = structured_linear(x.reshape(-1, x.shape[-1]), w, active_index, **kw)
    return y.reshape(*lead, w.shape[-1])


# ---------------------------------------------------------------------------
# tensor-parallel (shard-blocked) execution paths
#
# The TP exports in repro.sparse.formats reorganize each format's arrays into
# ``tp`` contiguous blocks along the neuron axis (global shapes unchanged;
# out_index / active_index locally rebased per block). These wrappers execute
# that layout as a ``jax.vmap`` over the block axis in plain jnp: under GSPMD
# with the block axis sharded over the 'model' mesh axis, every gather /
# matmul / scatter below is shard-local (the activation ``x`` stays
# replicated, so the stored indices are valid on every shard), and the single
# cross-device exchange is the all-gather XLA inserts when the (tp, B, wloc)
# partial outputs are reassembled into the replicated (B, d_out) activation.
# On one device the vmap formulation is just a reshape — bit-identical math —
# which is what makes the sharded stack testable on a simulated mesh.
#
# Pure jnp rather than Pallas: pallas_call is opaque to GSPMD propagation, so
# a sharded Pallas dispatch would need shard_map plumbing through every apply
# call site; the jnp formulation partitions for free and the per-shard shapes
# stay available to the autotune cache keys (formats.tuning_key shrinks by
# 1/tp) for a later shard_map'd kernel. Inference-only: no custom VJPs.
# ---------------------------------------------------------------------------


def condensed_linear_tp_nd(x: jax.Array, values: jax.Array,
                           indices: jax.Array, tp: int, *,
                           scales: jax.Array | None = None) -> jax.Array:
    """Condensed gather over ``tp`` contiguous neuron blocks.

    values/indices: (n, k) with ``n = tp * (n // tp)`` rows grouped by block
    (the plain condensed layout already is — contiguous rows partition).
    ``scales``: optional (n,) per-neuron dequant scales (quantized storage).
    """
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    n, k = values.shape
    npt = n // tp
    v = values.reshape(tp, npt, k)
    i = indices.reshape(tp, npt, k)

    def shard(v_s, i_s):
        g = jnp.take(x2, i_s, axis=1)                    # (B, npt, k) local
        return jnp.sum(g * v_s[None].astype(x2.dtype), axis=-1)

    y = jax.vmap(shard)(v, i)                            # (tp, B, npt)
    if scales is not None:
        y = y * scales.reshape(tp, 1, npt).astype(y.dtype)
    return jnp.moveaxis(y, 0, 1).reshape(x2.shape[0], n).reshape(*lead, n)


def condensed_over_active_linear_tp_nd(
        x: jax.Array, values: jax.Array, indices: jax.Array,
        out_index: jax.Array, d_out: int, tp: int, *,
        scales: jax.Array | None = None) -> jax.Array:
    """Condensed-over-active gather + LOCAL scatter over ``tp`` blocks.

    values/indices: (tp * a_tp, k) surviving-row arrays grouped by block;
    ``out_index``: (tp * a_tp,) int32 LOCALLY REBASED scatter positions in
    ``[0, d_out // tp)`` with the per-shard sentinel ``d_out // tp`` marking
    padding rows (dropped by the local scatter).
    """
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    a, k = values.shape
    a_tp = a // tp
    wloc = d_out // tp
    v = values.reshape(tp, a_tp, k)
    i = indices.reshape(tp, a_tp, k)
    oi = out_index.reshape(tp, a_tp)
    s = scales.reshape(tp, a_tp) if scales is not None else None

    def shard(v_s, i_s, oi_s, s_s):
        g = jnp.take(x2, i_s, axis=1)                    # (B, a_tp, k) local
        y_act = jnp.sum(g * v_s[None].astype(x2.dtype), axis=-1)
        if s_s is not None:
            y_act = y_act * s_s[None].astype(y_act.dtype)
        y_s = jnp.zeros((x2.shape[0], wloc), y_act.dtype)
        return y_s.at[:, oi_s].add(y_act, mode="drop")   # local positions

    if s is None:
        y = jax.vmap(lambda v_s, i_s, oi_s: shard(v_s, i_s, oi_s, None))(
            v, i, oi)
    else:
        y = jax.vmap(shard)(v, i, oi, s)
    return (jnp.moveaxis(y, 0, 1).reshape(x2.shape[0], d_out)
            .reshape(*lead, d_out))


def structured_linear_tp_nd(x: jax.Array, w: jax.Array,
                            active_index: jax.Array, tp: int) -> jax.Array:
    """Column-gathered structured matmul over ``tp`` output blocks.

    ``w``: the live dense (d_in, d_out) weight (its out dim shards over
    'model' under the standard column-parallel rules, so the block reshape
    keeps the gather shard-local); ``active_index``: (tp * a_tp,) int32
    LOCALLY REBASED surviving-column ids, sentinel ``d_out // tp``.
    """
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    d_in, d_out = w.shape
    wloc = d_out // tp
    a_tp = active_index.shape[0] // tp
    wb = jnp.moveaxis(w.reshape(d_in, tp, wloc), 1, 0)   # (tp, d_in, wloc)
    ai = active_index.reshape(tp, a_tp)

    def shard(w_s, ai_s):
        cols = jnp.take(w_s, jnp.minimum(ai_s, wloc - 1), axis=1)
        cols = jnp.where((ai_s < wloc)[None, :], cols, 0).astype(x2.dtype)
        y_act = x2 @ cols                                # (B, a_tp)
        y_s = jnp.zeros((x2.shape[0], wloc), y_act.dtype)
        return y_s.at[:, ai_s].add(y_act, mode="drop")

    y = jax.vmap(shard)(wb, ai)                          # (tp, B, wloc)
    return (jnp.moveaxis(y, 0, 1).reshape(x2.shape[0], d_out)
            .reshape(*lead, d_out))


def structured_gathered_linear_tp_nd(x: jax.Array, panel: jax.Array,
                                     active_index: jax.Array, d_out: int,
                                     tp: int) -> jax.Array:
    """Pre-gathered structured matmul over ``tp`` blocks (quantized
    StructuredFanIn storage: the (d_in, tp * a_tp) panel's columns are
    grouped by block; ``active_index`` locally rebased as above)."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    d_in = panel.shape[0]
    wloc = d_out // tp
    a_tp = active_index.shape[0] // tp
    pb = jnp.moveaxis(panel.reshape(d_in, tp, a_tp), 1, 0)  # (tp, d_in, a_tp)
    ai = active_index.reshape(tp, a_tp)

    def shard(p_s, ai_s):
        y_act = x2 @ p_s.astype(x2.dtype)                # (B, a_tp)
        y_s = jnp.zeros((x2.shape[0], wloc), y_act.dtype)
        return y_s.at[:, ai_s].add(y_act, mode="drop")

    y = jax.vmap(shard)(pb, ai)                          # (tp, B, wloc)
    return (jnp.moveaxis(y, 0, 1).reshape(x2.shape[0], d_out)
            .reshape(*lead, d_out))


def structured_gathered_linear_nd(x: jax.Array, panel: jax.Array,
                                  active_index: jax.Array, d_out: int, *,
                                  values_dtype: str | None = None,
                                  **kw) -> jax.Array:
    """Structured matmul over a caller-supplied compact (d_in, a) panel —
    the serving entry for quantized StructuredFanIn storage, whose stored
    representation IS the compact panel (dequantized in XLA before this
    call; no dense weight exists to gather from). Inference-only: no custom
    VJP. Tuned blocks resolve under the same ``kind="structured"`` keys as
    ``structured_linear`` — the kernels are identical, only the gather pass
    is absent."""
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    bb, bn = _resolve_blocks(x2.shape[0], x2.shape[-1],
                             active_index.shape[0], 0, kw.pop("block_b", None),
                             kw.pop("block_n", None),
                             jnp.dtype(x.dtype).itemsize, kind="structured",
                             scatter_width=d_out, values_dtype=values_dtype)
    y = sm.structured_matmul_pregathered(x2, panel.astype(x.dtype),
                                         active_index, d_out,
                                         block_b=bb, block_n=bn, **kw)
    return y.reshape(*lead, d_out)
