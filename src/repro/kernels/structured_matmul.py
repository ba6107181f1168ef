"""Ablation-aware Pallas kernels: column-gathered structured matmul and the
fused condensed-over-active scatter epilogue.

Both kernels execute the neuron-ablation half of the paper's Fig. 4 serving
story so that the ablated fraction converts into REAL byte/FLOP savings
instead of a masked-out dense pass:

* ``structured_matmul`` — the "structured" Fig. 4 point. The surviving
  output columns of the dense weight are gathered through a precomputed
  ``active_index`` int32 vector (surviving column ids, padded to the 128-lane
  tile with the out-of-range sentinel ``d_out``), the matmul runs over ONLY
  those ``a_pad`` columns on the MXU, and a fused one-hot scatter epilogue
  writes each compact column back to its dense position — ablated neurons
  are exact zeros written in-kernel, never a separate XLA scatter dispatch.
  Per-step HBM weight bytes and MXU matmul FLOPs are ``a_pad / d_out`` of
  the dense path. The column gather itself (``jnp.take`` along the lane
  axis) happens once per compiled program: the weight and ``active_index``
  are loop-invariant in the decode ``lax.scan``, so XLA hoists the gather
  out of the token loop and every decode step streams only the compact
  ``(d_in, a_pad)`` panel. The gather is tagged with
  ``jax.named_scope("hoisted_column_gather")`` so HLO tests can count it;
  the scalar-prefetch decode variant below removes it entirely.
* ``structured_matmul_pregathered`` — same kernels, but the caller supplies
  the compact ``(d_in, a_pad)`` panel directly (e.g. dequantized from
  int8/fp8 quantized storage, where no dense ``d_in x d_out`` weight exists
  to gather from). No gather pass appears in the program at all.
* ``condensed_over_active_matmul`` — the combined Fig. 4 point, fused. The
  condensed constant fan-in gather (``condensed_matmul._gather_reduce``,
  the same VMEM-local lane-chunk gather) runs over the ``a <= d_out``
  surviving rows and the SAME one-hot epilogue scatters each row through
  ``out_index`` into the dense output layout inside the kernel. This replaces the previous
  compose-then-scatter lowering (``y.at[:, out_index].add``) that wrote the
  compact activations to HBM and re-read them in a separate scatter op —
  one full activation round trip per layer on the decode hot path. With
  ``scales`` (per-row f32), ``values`` are int8/fp8 codes and the
  dequantize fuses into the kernel (one multiply per compact row output,
  after the k-reduction — exact, the scale is constant over a row's
  fan-in), so the weight stream shrinks to ~1 byte/elem.

Scatter epilogue (shared): for an index tile ``ai`` (compact position ->
dense column, padding == ``d_out``) the kernel builds the one-hot selection
matrix ``sel[t, c] = (ai[t] == c)`` and accumulates ``y_tile @ sel`` into a
``(B_blk, d_out)`` output block that stays resident across the compact-tile
grid dimension (innermost, same accumulation pattern as the dw kernel in
condensed_matmul). This is the Mosaic-friendly scatter formulation: an MXU
matmul instead of a data-dependent store. Exactness: each dense column is
hit by exactly one compact slot (export guarantees unique indices), a
one-hot dot passes the value through bit-exactly (v * 1.0 + exact zeros),
and padding slots (``ai == d_out``) match no column, so they are dropped
exactly like the old ``mode="drop"`` scatter.

Out-blocked epilogue (``block_o``): the default kernels keep the full
``(B_blk, d_out)`` output block and ``(N_blk, d_out)`` one-hot tile resident
in VMEM — fine to ``d_out ~ 8k``, not beyond. Passing ``block_o`` (a
128-multiple) adds a ``d_out`` tile axis to the grid: the one-hot is built
against tile-local columns (``iota + o * block_o``) and only a
``(B_blk, block_o)`` output block + ``(N_blk, block_o)`` one-hot tile stay
resident. Cost: each compact tile's ``y`` is recomputed once per ``d_out``
tile (the compact->dense mapping is data-dependent, so every (o, j) pair
must be visited) — a FLOP-for-VMEM trade that only pays off when ``d_out``
does not fit; bit-identical to the unblocked epilogue (each dense column
still matched by exactly one (o, j) one-hot hit).

Scalar-prefetch decode variant (``prefetch_gather``): the decode-scan gather
hoist above still costs one XLA gather pass per compiled program plus an
HBM round trip for the ``(d_in, a_pad)`` panel. The prefetch variant
(``pltpu.PrefetchScalarGridSpec``) instead prefetches ``active_index`` as a
scalar operand, stages the FULL dense ``(d_in, d_out)`` weight in VMEM, and
performs the column gather inside the kernel per compact tile — no XLA
gather pass, no intermediate panel buffer. The price is full-weight VMEM
residency (``d_in * d_out`` words), so it is gated on the VMEM budget and
applies to decode shapes; enable via ``prefetch_gather=True`` or
``REPRO_PREFETCH_GATHER=1``. It runs in interpret mode only: Mosaic refuses
the kernel for a TPU ("Can only load scalars from SMEM": the prefetched
index vector is sliced as a vector), so it stays off the main path.

VMEM budgets (words; ``d_in`` is structurally unblocked — the gather needs
the whole activation row; ``d_out`` is unblocked only when ``block_o`` is
not used):

    structured: B_blk*d_in + d_in*N_blk + N_blk + B_blk*N_blk
                + N_blk*O_blk + B_blk*O_blk          (O_blk = block_o or d_out)
    coa fused:  B_blk*d_in + N_blk*k*3 + N_blk + B_blk*N_blk
                + N_blk*O_blk + B_blk*O_blk
    prefetch:   B_pad*d_in + d_in*d_out + N_blk + B_pad*d_out + N_blk*d_out

checked against the same per-backend cap as ``condensed_matmul``
(``vmem_budget_bytes`` — 16 MiB/core published v5e figure, halved for
double-buffering headroom, overridable via ``REPRO_VMEM_CAP_BYTES``; see
that module's docstring for the Mosaic scoped-VMEM-limit cross-check).
Quantized tiles are charged at 4 B/elem like everything else — conservative
for 1-byte codes, so a block that fits at f32 always fits quantized. The
``N_blk*d_out`` one-hot tile is the dominant term at large ``d_out``; the
budget shrinks the blocks accordingly, and the (8, 128) minimum is kept
even over budget (documented stance shared with
``condensed_matmul._aligned_candidates``). Decode shapes (B <=
``SMALL_BATCH_MAX``) use specialized variants that stage the sublane-padded
batch whole. ``repro.sparse.autotune`` runs the timed block search under the
``kind="structured"`` tuning keys.

Validated bit-identical against ``kernels.ops.structured_dense`` (structured)
and token-identical to the masked path (COA) in interpret mode on CPU.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import condensed_matmul as cm

LANE = cm.LANE
SUBLANE = cm.SUBLANE
SMALL_BATCH_MAX = cm.SMALL_BATCH_MAX
_ceil_to = cm._ceil_to


def padded_active_count(a: int, d_out: int) -> int:
    """Exported ``active_index`` length: the realized active-column count
    rounded up to the 128-lane tile (the gather axis is the lane dimension),
    capped at the padded dense width — padding past ``d_out`` buys nothing.
    Accepts float ``a`` (the cost model prices fractional row counts)."""
    return min(_ceil_to(int(max(a, 1)), LANE), _ceil_to(int(max(d_out, 1)), LANE))


# ---------------------------------------------------------------------------
# VMEM budget formulas / block candidates
# ---------------------------------------------------------------------------


def structured_vmem_words(block_b: int, block_n: int, d_in: int,
                          d_out: int, block_o: int | None = None) -> int:
    """x tile + gathered-weight tile + index tile + compact-y tile + one-hot
    tile + resident output block (``block_o`` tiles the last two)."""
    o_blk = min(block_o or d_out, d_out)
    return (block_b * d_in + d_in * block_n + block_n + block_b * block_n
            + block_n * o_blk + block_b * o_blk)


def coa_vmem_words(block_b: int, block_n: int, d_in: int, k: int,
                   d_out: int, block_o: int | None = None) -> int:
    """x tile + (values + indices) tiles + the values tile widened to f32 +
    out_index tile + compact-y tile + one-hot tile + resident output block
    (``block_o`` tiles the last two)."""
    o_blk = min(block_o or d_out, d_out)
    return (block_b * d_in + block_n * k * 3 + block_n + block_b * block_n
            + block_n * o_blk + block_b * o_blk)


def prefetch_vmem_words(b_pad: int, block_n: int, d_in: int,
                        d_out: int) -> int:
    """Scalar-prefetch decode working set: whole batch + FULL dense weight +
    compact-y tile + resident dense output block + one-hot tile."""
    return (b_pad * d_in + d_in * d_out + b_pad * block_n
            + b_pad * d_out + block_n * d_out)


def structured_block_candidates(b: int, d_in: int, a: int, d_out: int, *,
                                backend: str | None = None) -> list[tuple[int, int]]:
    """8x128-aligned shapes fitting structured_vmem_words; ``a`` is the
    compact row count the grid tiles over (condensed_matmul's enumeration,
    including its keep-the-minimum-over-budget stance, adapted via a words
    lambda)."""
    return cm._aligned_candidates(
        lambda bb, bn, _d, _k: structured_vmem_words(bb, bn, d_in, d_out),
        b, 0, a, 0, backend)


def coa_block_candidates(b: int, d_in: int, a: int, k: int, d_out: int, *,
                         backend: str | None = None) -> list[tuple[int, int]]:
    """8x128-aligned shapes fitting coa_vmem_words over the ``a`` surviving
    rows (see structured_block_candidates)."""
    return cm._aligned_candidates(
        lambda bb, bn, _d, _k: coa_vmem_words(bb, bn, d_in, k, d_out),
        b, 0, a, 0, backend)


def default_structured_blocks(b: int, d_in: int, a: int, d_out: int, *,
                              backend: str | None = None) -> tuple[int, int]:
    return cm.pick_default_blocks(
        structured_block_candidates(b, d_in, a, d_out, backend=backend), b, a)


def default_coa_blocks(b: int, d_in: int, a: int, k: int, d_out: int, *,
                       backend: str | None = None) -> tuple[int, int]:
    return cm.pick_default_blocks(
        coa_block_candidates(b, d_in, a, k, d_out, backend=backend), b, a)


def _prefetch_default() -> bool:
    return os.environ.get("REPRO_PREFETCH_GATHER", "0") != "0"


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _onehot_scatter(y: jax.Array, idx_row: jax.Array, d_out: int,
                    col_offset=0) -> jax.Array:
    """Scatter a compact (B_blk, N_blk) tile to dense columns via a one-hot
    MXU matmul. ``idx_row``: (1, N_blk) int32 dense positions; out-of-range
    entries (== d_out) match no column and are dropped exactly. Exact: each
    surviving value is multiplied by 1.0 and summed with exact zeros.
    ``col_offset`` shifts the column window for out-blocked epilogues (the
    tile then covers dense columns [col_offset, col_offset + width))."""
    cols = jax.lax.broadcasted_iota(jnp.int32, (idx_row.shape[1], d_out), 1)
    sel = (idx_row.T == cols + col_offset).astype(jnp.float32)  # (N_blk, O)
    return jnp.dot(y, sel, preferred_element_type=jnp.float32)


def _structured_kernel(x_ref, w_ref, ai_ref, out_ref, *, grid_axis: int,
                       o_axis: int | None = None, block_o: int | None = None):
    """One compact-column tile of the gathered structured matmul.

    x_ref  : (B_blk, d_in)    VMEM
    w_ref  : (d_in, N_blk)    VMEM — pre-gathered surviving columns
    ai_ref : (1, N_blk)       VMEM int32 — dense position of each column
    out_ref: (B_blk, d_out)   VMEM — resident across the compact-tile axis
             ((B_blk, block_o) when the epilogue is out-blocked; the one-hot
             then selects only this tile's column window)
    """
    j = pl.program_id(grid_axis)
    y = jnp.dot(x_ref[...].astype(jnp.float32),
                w_ref[...].astype(jnp.float32),
                preferred_element_type=jnp.float32)      # (B_blk, N_blk)
    offset = 0 if o_axis is None else pl.program_id(o_axis) * block_o
    contrib = _onehot_scatter(y, ai_ref[...], out_ref.shape[-1], offset)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = contrib.astype(out_ref.dtype)

    @pl.when(j != 0)
    def _accumulate():
        out_ref[...] = out_ref[...] + contrib.astype(out_ref.dtype)


def _structured_prefetch_kernel(ai_ref, x_ref, w_ref, out_ref, *,
                                block_n: int):
    """Scalar-prefetch decode kernel: ``ai_ref`` is the PREFETCHED compact
    index vector (whole (a_pad,) int32, SMEM), ``w_ref`` the FULL dense
    (d_in, d_out) weight staged in VMEM. The column gather runs in-kernel
    per compact tile — no XLA gather pass, no (d_in, a_pad) panel buffer.

    x_ref  : (B_pad, d_in)   VMEM, whole sublane-padded batch
    out_ref: (B_pad, d_out)  VMEM, resident across the grid
    """
    j = pl.program_id(0)
    d_out = out_ref.shape[-1]
    idx = jax.lax.dynamic_slice(ai_ref[...], (j * block_n,), (block_n,))
    # padding entries (== d_out) clip to the last column; their (finite)
    # products are dropped by the all-zero one-hot row at scatter time
    wg = jnp.take(w_ref[...].astype(jnp.float32),
                  jnp.minimum(idx, d_out - 1), axis=1)   # (d_in, N_blk)
    y = jnp.dot(x_ref[...].astype(jnp.float32), wg,
                preferred_element_type=jnp.float32)
    contrib = _onehot_scatter(y, idx.reshape(1, block_n), d_out)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = contrib.astype(out_ref.dtype)

    @pl.when(j != 0)
    def _accumulate():
        out_ref[...] = out_ref[...] + contrib.astype(out_ref.dtype)


def _coa_kernel(x_ref, wt_ref, it_ref, oi_ref, *rest, grid_axis: int,
                d_out: int, round_to=None, scaled: bool = False,
                widen: bool = False, o_axis: int | None = None,
                block_o: int | None = None):
    """One surviving-row tile of the fused condensed-over-active matmul:
    the condensed gather-reduce (``condensed_matmul._gather_reduce``)
    followed by the scatter epilogue.

    x_ref  : (B_blk, d_pad) f32   wt_ref/it_ref : (k, N_blk), as stored
    oi_ref : (1, N_blk)
    out_ref: (B_blk, d_out) resident across the row-tile axis ((B_blk,
    block_o) when out-blocked). ``scaled`` inserts a (1, N_blk) per-row f32
    scale tile before the output ref: ``wt_ref`` then holds int8/fp8 codes
    and the dequantize multiply fuses here, after the k-reduction (exact —
    the scale is constant over a row's fan-in). ``widen`` appends the f32
    scratch ``condensed_matmul._values_f32`` widens values into. Rows whose
    ``out_index`` is the sentinel ``d_out`` (export padding, or the
    unspecified rows of a tile that overhangs ``a``) are zeroed before the
    one-hot matmul, which would otherwise spread a non-finite value over
    every column.
    """
    scratch = rest[-1:] if widen else ()
    rest = rest[:-1] if widen else rest
    scale_ref = rest[0] if scaled else None
    out_ref = rest[-1]
    wt = cm._values_f32(wt_ref, scratch, round_to)
    j = pl.program_id(grid_axis)
    y = cm._gather_reduce(x_ref, wt, it_ref)             # (B_blk, N_blk) f32
    if scaled:
        y = y * scale_ref[...]                           # (1, N_blk) bcast
    y = jnp.where(oi_ref[...] < d_out, y, 0.0)
    offset = 0 if o_axis is None else pl.program_id(o_axis) * block_o
    contrib = _onehot_scatter(y, oi_ref[...], out_ref.shape[-1], offset)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = contrib.astype(out_ref.dtype)

    @pl.when(j != 0)
    def _accumulate():
        out_ref[...] = out_ref[...] + contrib.astype(out_ref.dtype)


# ---------------------------------------------------------------------------
# pallas_call wrappers — structured
# ---------------------------------------------------------------------------


def _gather_columns(w: jax.Array, active_index: jax.Array) -> jax.Array:
    """(d_in, a) panel of surviving columns. Padding entries clip to the last
    column — their (garbage but finite) products are dropped by the all-zero
    one-hot row at scatter time, so no masking multiply is needed.

    Wrapped in ``jax.named_scope("hoisted_column_gather")``: this is the ONE
    XLA gather pass the decode scan hoists (loop-invariant operands), and
    the scope tag is what the HLO dispatch-count tests — and the assertion
    that the scalar-prefetch variant removes it — key on."""
    with jax.named_scope("hoisted_column_gather"):
        d_out = w.shape[-1]
        return jnp.take(w, jnp.minimum(active_index, d_out - 1), axis=1)


@functools.partial(jax.jit, static_argnames=("d_out", "block_b", "block_n",
                                             "block_o", "interpret"))
def _structured_tiled(x, wa, active_index, *, d_out: int, block_b: int,
                      block_n: int, block_o: int | None, interpret: bool):
    """General gathered matmul over a PRE-GATHERED (d_in, a) panel: grid
    (batch tiles, compact-column tiles), plus a d_out tile axis when the
    epilogue is out-blocked."""
    b, d_in = x.shape
    a = active_index.shape[0]
    bp, ap = _ceil_to(max(b, 1), block_b), _ceil_to(max(a, 1), block_n)
    xp = jnp.pad(x, ((0, bp - b), (0, 0)))
    wap = jnp.pad(wa, ((0, 0), (0, ap - a)))
    aip = jnp.pad(active_index.astype(jnp.int32), (0, ap - a),
                  constant_values=d_out).reshape(1, ap)

    if block_o is None:
        out = pl.pallas_call(
            functools.partial(_structured_kernel, grid_axis=1),
            grid=(bp // block_b, ap // block_n),
            in_specs=[
                pl.BlockSpec((block_b, d_in), lambda i, j: (i, 0)),
                pl.BlockSpec((d_in, block_n), lambda i, j: (0, j)),
                pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
            ],
            out_specs=pl.BlockSpec((block_b, d_out), lambda i, j: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((bp, d_out), x.dtype),
            interpret=interpret,
        )(xp, wap, aip)
        return out[:b]

    dop = _ceil_to(d_out, block_o)
    out = pl.pallas_call(
        functools.partial(_structured_kernel, grid_axis=2, o_axis=1,
                          block_o=block_o),
        grid=(bp // block_b, dop // block_o, ap // block_n),
        in_specs=[
            pl.BlockSpec((block_b, d_in), lambda i, o, j: (i, 0)),
            pl.BlockSpec((d_in, block_n), lambda i, o, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda i, o, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((block_b, block_o), lambda i, o, j: (i, o)),
        out_shape=jax.ShapeDtypeStruct((bp, dop), x.dtype),
        interpret=interpret,
    )(xp, wap, aip)
    return out[:b, :d_out]


@functools.partial(jax.jit, static_argnames=("d_out", "block_n", "block_o",
                                             "interpret"))
def _structured_decode(x, wa, active_index, *, d_out: int, block_n: int,
                       block_o: int | None, interpret: bool):
    """Decode-specialized variant: sublane-padded batch staged whole, grid
    over compact-column tiles only (plus a d_out tile axis when
    out-blocked)."""
    b, d_in = x.shape
    a = active_index.shape[0]
    bp, ap = _ceil_to(max(b, 1), SUBLANE), _ceil_to(max(a, 1), block_n)
    xp = jnp.pad(x, ((0, bp - b), (0, 0)))
    wap = jnp.pad(wa, ((0, 0), (0, ap - a)))
    aip = jnp.pad(active_index.astype(jnp.int32), (0, ap - a),
                  constant_values=d_out).reshape(1, ap)

    if block_o is None:
        out = pl.pallas_call(
            functools.partial(_structured_kernel, grid_axis=0),
            grid=(ap // block_n,),
            in_specs=[
                pl.BlockSpec((bp, d_in), lambda j: (0, 0)),
                pl.BlockSpec((d_in, block_n), lambda j: (0, j)),
                pl.BlockSpec((1, block_n), lambda j: (0, j)),
            ],
            out_specs=pl.BlockSpec((bp, d_out), lambda j: (0, 0)),
            out_shape=jax.ShapeDtypeStruct((bp, d_out), x.dtype),
            interpret=interpret,
        )(xp, wap, aip)
        return out[:b]

    dop = _ceil_to(d_out, block_o)
    out = pl.pallas_call(
        functools.partial(_structured_kernel, grid_axis=1, o_axis=0,
                          block_o=block_o),
        grid=(dop // block_o, ap // block_n),
        in_specs=[
            pl.BlockSpec((bp, d_in), lambda o, j: (0, 0)),
            pl.BlockSpec((d_in, block_n), lambda o, j: (0, j)),
            pl.BlockSpec((1, block_n), lambda o, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bp, block_o), lambda o, j: (0, o)),
        out_shape=jax.ShapeDtypeStruct((bp, dop), x.dtype),
        interpret=interpret,
    )(xp, wap, aip)
    return out[:b, :d_out]


@functools.partial(jax.jit, static_argnames=("d_out", "block_n", "interpret"))
def _structured_prefetch_decode(x, w, active_index, *, d_out: int,
                                block_n: int, interpret: bool):
    """Scalar-prefetch decode: active_index prefetched scalar, FULL dense
    weight staged in VMEM, gather in-kernel (see module docstring)."""
    b, d_in = x.shape
    a = active_index.shape[0]
    bp, ap = _ceil_to(max(b, 1), SUBLANE), _ceil_to(max(a, 1), block_n)
    xp = jnp.pad(x, ((0, bp - b), (0, 0)))
    aip = jnp.pad(active_index.astype(jnp.int32), (0, ap - a),
                  constant_values=d_out)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(ap // block_n,),
        in_specs=[
            pl.BlockSpec((bp, d_in), lambda j, ai: (0, 0)),
            pl.BlockSpec((d_in, d_out), lambda j, ai: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bp, d_out), lambda j, ai: (0, 0)),
    )
    out = pl.pallas_call(
        functools.partial(_structured_prefetch_kernel, block_n=block_n),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((bp, d_out), x.dtype),
        interpret=interpret,
    )(aip, xp, w)
    return out[:b]


def structured_matmul(
    x: jax.Array,
    w: jax.Array,
    active_index: jax.Array,
    *,
    block_b: int | None = None,
    block_n: int | None = None,
    block_o: int | None = None,
    interpret: bool | None = None,
    prefetch_gather: bool | None = None,
) -> jax.Array:
    """Column-gathered structured matmul. x: (B, d_in), w: (d_in, d_out),
    active_index: (a,) int32 surviving-column ids (out-of-range == padding).
    Returns (B, d_out) with ablated columns exact zeros.

    ``block_b=None`` routes decode shapes (B <= SMALL_BATCH_MAX) to the
    decode-specialized variant; otherwise the VMEM-budget default applies
    (``repro.sparse.autotune`` supplies timed choices through
    ``kernels.ops.structured_linear``). ``block_o`` tiles the scatter
    epilogue over d_out (see module docstring); ``prefetch_gather`` selects
    the scalar-prefetch decode variant. Bit-identical to
    ``kernels.ops.structured_dense`` for any active set.
    """
    b, d_in = x.shape
    d_out = w.shape[-1]
    a = active_index.shape[0]
    if interpret is None:
        interpret = cm.default_interpret()
    if block_b is None and b <= SMALL_BATCH_MAX:
        return structured_matmul_decode(
            x, w, active_index, block_n=block_n, block_o=block_o,
            interpret=interpret, prefetch_gather=prefetch_gather)
    if block_b is None and block_n is None:
        block_b, block_n = default_structured_blocks(b, d_in, a, d_out)
    elif block_b is None:
        block_b = cm._fit_block_b(
            lambda bb, bn, _d, _k: structured_vmem_words(bb, bn, d_in, d_out),
            block_n, b, d_in, 0, cap=128)
    elif block_n is None:
        block_n = cm._fit_block_n(
            lambda bb, bn, _d, _k: structured_vmem_words(bb, bn, d_in, d_out),
            block_b, a, d_in, 0, cap=128)
    wa = _gather_columns(w, active_index)
    return _structured_tiled(x, wa, active_index, d_out=d_out,
                             block_b=block_b, block_n=block_n,
                             block_o=block_o, interpret=interpret)


def structured_matmul_decode(
    x: jax.Array,
    w: jax.Array,
    active_index: jax.Array,
    *,
    block_n: int | None = None,
    block_o: int | None = None,
    interpret: bool | None = None,
    prefetch_gather: bool | None = None,
) -> jax.Array:
    """Decode-specialized structured matmul (batch staged whole). Bit-
    identical to the general variant: the d_in contraction and the one-hot
    scatter are independent of how the batch axis is padded or tiled.

    ``prefetch_gather=True`` forces the scalar-prefetch variant (caller
    takes responsibility for VMEM); ``None`` consults
    ``REPRO_PREFETCH_GATHER`` and additionally gates on the VMEM budget —
    full-weight residency is the variant's price (see prefetch_vmem_words).
    """
    b, d_in = x.shape
    d_out = w.shape[-1]
    a = active_index.shape[0]
    if interpret is None:
        interpret = cm.default_interpret()
    if block_n is None:
        _, block_n = default_structured_blocks(min(b, SMALL_BATCH_MAX), d_in,
                                               a, d_out)
    use_prefetch = prefetch_gather
    if use_prefetch is None and block_o is None:
        bp = _ceil_to(max(b, 1), SUBLANE)
        fits = (prefetch_vmem_words(bp, block_n, d_in, d_out) * cm._WORD
                <= cm.vmem_budget_bytes())
        use_prefetch = _prefetch_default() and fits
    if use_prefetch:
        return _structured_prefetch_decode(x, w, active_index, d_out=d_out,
                                           block_n=block_n,
                                           interpret=interpret)
    wa = _gather_columns(w, active_index)
    return _structured_decode(x, wa, active_index, d_out=d_out,
                              block_n=block_n, block_o=block_o,
                              interpret=interpret)


def structured_matmul_pregathered(
    x: jax.Array,
    panel: jax.Array,
    active_index: jax.Array,
    d_out: int,
    *,
    block_b: int | None = None,
    block_n: int | None = None,
    block_o: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Structured matmul over a caller-supplied compact panel.

    ``panel``: (d_in, a) surviving columns, already gathered — the entry
    point for quantized StructuredFanIn storage, where the compact panel IS
    the stored representation (dequantized in XLA) and no dense weight
    exists to gather from. Same kernels, no gather pass in the program.
    """
    b, d_in = x.shape
    a = active_index.shape[0]
    if interpret is None:
        interpret = cm.default_interpret()
    if block_b is None and b <= SMALL_BATCH_MAX:
        if block_n is None:
            _, block_n = default_structured_blocks(min(b, SMALL_BATCH_MAX),
                                                   d_in, a, d_out)
        return _structured_decode(x, panel, active_index, d_out=d_out,
                                  block_n=block_n, block_o=block_o,
                                  interpret=interpret)
    if block_b is None and block_n is None:
        block_b, block_n = default_structured_blocks(b, d_in, a, d_out)
    elif block_b is None:
        block_b = cm._fit_block_b(
            lambda bb, bn, _d, _k: structured_vmem_words(bb, bn, d_in, d_out),
            block_n, b, d_in, 0, cap=128)
    elif block_n is None:
        block_n = cm._fit_block_n(
            lambda bb, bn, _d, _k: structured_vmem_words(bb, bn, d_in, d_out),
            block_b, a, d_in, 0, cap=128)
    return _structured_tiled(x, panel, active_index, d_out=d_out,
                             block_b=block_b, block_n=block_n,
                             block_o=block_o, interpret=interpret)


# ---------------------------------------------------------------------------
# pallas_call wrappers — condensed-over-active, fused epilogue
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("d_out", "block_b", "block_n",
                                             "block_o", "interpret"))
def _coa_tiled(x, values, indices, out_index, scales=None, *, d_out: int,
               block_b: int, block_n: int, block_o: int | None,
               interpret: bool):
    """Grid (batch tiles, surviving-row tiles), plus a d_out tile axis
    between them when the epilogue is out-blocked."""
    b = x.shape[0]
    a, k = values.shape
    bp, ap = _ceil_to(max(b, 1), block_b), _ceil_to(max(a, 1), block_n)
    xp = cm.pad_x(x, bp)
    # values/indices enter as stored, viewed (k, a) (a bitcast on a TPU;
    # see condensed_matmul); only the (a,) row vectors are padded to whole
    # tiles, out_index with the dropped sentinel
    operands = [xp, values.T, indices.astype(jnp.int32).T,
                jnp.pad(out_index.astype(jnp.int32), (0, ap - a),
                        constant_values=d_out).reshape(1, ap)]
    scaled = scales is not None
    if scaled:
        operands.append(jnp.pad(scales.astype(jnp.float32),
                                (0, ap - a)).reshape(1, ap))
    round_to = None if scaled else cm.round_dtype(x.dtype, values.dtype)
    scratch = cm.values_scratch(values.dtype, round_to, k, block_n)
    common = dict(d_out=d_out, scaled=scaled, round_to=round_to,
                  widen=bool(scratch))

    if block_o is None:
        grid = (bp // block_b, ap // block_n)
        dop, o_blk = d_out, d_out
        tile = lambda i, j: (i, j, 0)
        kernel = functools.partial(_coa_kernel, grid_axis=1, **common)
    else:
        grid = (bp // block_b, _ceil_to(d_out, block_o) // block_o,
                ap // block_n)
        dop, o_blk = _ceil_to(d_out, block_o), block_o
        tile = lambda i, o, j: (i, j, o)
        kernel = functools.partial(_coa_kernel, grid_axis=2, o_axis=1,
                                   block_o=block_o, **common)

    def spec(shape, pick):
        return pl.BlockSpec(shape, lambda *g: pick(*tile(*g)))

    in_specs = [spec((block_b, xp.shape[1]), lambda i, j, o: (i, 0)),
                spec((k, block_n), lambda i, j, o: (0, j)),
                spec((k, block_n), lambda i, j, o: (0, j)),
                spec((1, block_n), lambda i, j, o: (0, j))]
    if scaled:
        in_specs.append(spec((1, block_n), lambda i, j, o: (0, j)))
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=spec((block_b, o_blk), lambda i, j, o: (i, o)),
        out_shape=jax.ShapeDtypeStruct((bp, dop), x.dtype),
        scratch_shapes=scratch,
        interpret=interpret,
        name="condensed_over_active_matmul",
    )(*operands)
    return out[:b, :d_out]


def condensed_over_active_matmul(
    x: jax.Array,
    values: jax.Array,
    indices: jax.Array,
    out_index: jax.Array,
    d_out: int,
    *,
    scales: jax.Array | None = None,
    block_b: int | None = None,
    block_n: int | None = None,
    block_o: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Fused condensed-over-active matmul: the condensed gather runs over the
    ``a <= d_out`` surviving rows and the output block is written through
    ``out_index`` directly (ablated rows zero-filled in-kernel). Token-
    identical to the old compose-then-scatter lowering — the same f32
    accumulation, the same single downcast, the same drop semantics for
    out-of-range padding rows — without the separate scatter dispatch or the
    compact-activation HBM round trip.

    ``scales`` (shape (a,), f32) marks ``values`` as int8/fp8 codes; the
    dequantize fuses into the kernel. ``block_o`` tiles the scatter
    epilogue over d_out (see module docstring).
    """
    b, d_in = x.shape
    a, k = values.shape
    if interpret is None:
        interpret = cm.default_interpret()
    if block_b is None and b <= SMALL_BATCH_MAX:
        return condensed_over_active_matmul_decode(
            x, values, indices, out_index, d_out, scales=scales,
            block_n=block_n, block_o=block_o, interpret=interpret)
    if block_b is None and block_n is None:
        block_b, block_n = default_coa_blocks(b, d_in, a, k, d_out)
    elif block_b is None:
        block_b = cm._fit_block_b(
            lambda bb, bn, _d, _k: coa_vmem_words(bb, bn, d_in, k, d_out),
            block_n, b, d_in, k, cap=128)
    elif block_n is None:
        block_n = cm._fit_block_n(
            lambda bb, bn, _d, _k: coa_vmem_words(bb, bn, d_in, k, d_out),
            block_b, a, d_in, k, cap=128)
    return _coa_tiled(x, values, indices, out_index, scales, d_out=d_out,
                      block_b=block_b, block_n=block_n, block_o=block_o,
                      interpret=interpret)


def condensed_over_active_matmul_decode(
    x: jax.Array,
    values: jax.Array,
    indices: jax.Array,
    out_index: jax.Array,
    d_out: int,
    *,
    scales: jax.Array | None = None,
    block_n: int | None = None,
    block_o: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Decode-specialized fused COA matmul (batch staged whole)."""
    b, d_in = x.shape
    a, k = values.shape
    if interpret is None:
        interpret = cm.default_interpret()
    if block_n is None:
        _, block_n = default_coa_blocks(min(b, SMALL_BATCH_MAX), d_in, a, k,
                                        d_out)
    return _coa_tiled(x, values, indices, out_index, scales, d_out=d_out,
                      block_b=_ceil_to(max(b, 1), SUBLANE), block_n=block_n,
                      block_o=block_o, interpret=interpret)
