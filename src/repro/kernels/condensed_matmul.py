"""Pallas TPU kernel: condensed constant fan-in matmul (forward + dw).

TPU adaptation of the paper's Alg. 1 (a CPU loop / CUDA gather kernel):

* The whole input-feature axis of the current batch tile is staged in VMEM
  (``x_tile: (B_blk, d_in)``) so the per-neuron gathers are VMEM-local — the
  TPU analogue of CUDA shared-memory gathers. HBM traffic for the weights is
  exactly ``2 * n_out * k`` words (values + indices): sparsity converts
  directly into HBM-byte savings, which is what matters for the bandwidth-
  bound decode/online-inference shapes this kernel targets. The quantized
  variant (``scales`` passed) streams values at ONE byte per element
  (int8/fp8) plus ``4 * n_out`` bytes of per-neuron f32 scales; the
  dequantization is fused into the gather-reduce (one multiply per output
  element, after the k-reduction — exact, since the scale is per output
  neuron), so the HBM weight stream shrinks ~4x with no extra passes.
* Grid is (batch tiles x neuron tiles). The kernel reads values and
  indices in their stored dtype, viewed as (k, n_out) (``values.T``), so
  fan-in slot ``j`` of every neuron in a tile is one row. On a TPU that view
  costs nothing: XLA's default layout of an (n_out, k) array whose ``k`` is
  not a 128 multiple is k-major already (it pads least), so the transpose
  is a bitcast and the weights stream from HBM once, as stored
  (``tests/test_tpu_compile.py`` checks the compiled program for any copy).
  Values that are not f32, or f32 values under narrower activations, are
  widened in VMEM once per grid step, rounded first to the activations'
  dtype (the product the reference forms). For each slot the kernel gathers
  ``x_tile[:, idx[j]]`` on the VPU and adds it times the values row to an
  f32 running sum over the k slots. Mosaic gathers along lanes only inside
  one 128-lane vreg, so each gather walks the ``d_in / 128`` lane chunks of
  ``x`` and keeps, per lane, the chunk its index falls in: ``d_in / 128``
  gathers per slot and 128 neurons. ``x`` is widened to f32 (the lane
  gather is 32-bit only) and ``d_in`` padded to a 128 multiple. A last
  neuron tile that overhangs ``n_out`` reads unspecified columns, whose
  outputs are never written back.
* ``d_in`` is NOT blocked (constant fan-in indices may reference any input
  feature), so the block shape must satisfy the VMEM budget

      forward:  B_blk*d_in + N_blk*k*3 + B_blk*N_blk          words
      dw:       B_blk*N_blk + B_blk*d_in + 2*N_blk*k          words
                (dy tile      x tile       idx tile + dw tile)

  (the forward's third ``N_blk*k`` is the f32 copy of a values tile that
  is widened in VMEM) against the per-backend VMEM cap. The 16 MiB/core
  figure in ``VMEM_BYTES``
  is the published v5e (and v4) per-core VMEM size; Mosaic's ACTUAL
  per-kernel budget is the scoped-VMEM limit the compiler enforces
  (``pltpu.CompilerParams(vmem_limit_bytes=...)`` /
  ``xla_tpu_scoped_vmem_limit_kib``), which defaults to less than the full
  core VMEM — that is why only ``VMEM_USABLE_FRACTION`` (half) of the cap is
  budgeted here, leaving room for double buffering and compiler temporaries.
  On parts with a different VMEM size, or to mirror an explicitly lowered
  ``vmem_limit_bytes``, override the cap with ``REPRO_VMEM_CAP_BYTES``
  (bytes; the usable fraction still applies). The budget formulas charge
  every tile at 4 B/elem even for 1-byte quantized values — conservative by
  ``3 * N_blk * k`` bytes, so a block that fits at f32 always fits
  quantized. ``block_candidates`` / ``dw_block_candidates`` enumerate the
  8x128-aligned shapes that fit; ``default_blocks`` picks an untimed default
  and ``repro.sparse.autotune`` runs the timed search.
* Decode shapes (B <= 8) use a specialized variant: the grid runs over
  neuron tiles only and the (sublane-padded) batch is staged whole, so a
  B=1 request does not pay for a 128-row batch tile of padding.
* The dw kernel is blocked over batch tiles (accumulating into the output
  block across the innermost grid dimension), so large-batch training shapes
  never stage the full batch in VMEM.
* ``interpret`` follows the backend: interpret mode on the CPU, compiled
  Mosaic kernels on a TPU, with no override.

Validated against ``ref.condensed_matmul_ref`` in interpret mode (CPU);
``tests/test_tpu_compile.py`` compiles every kernel for a described v5e.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# TPU tiling units (f32): last dim 128 lanes, second-to-last 8 sublanes.
LANE = 128
SUBLANE = 8

# Decode-specialized variant threshold: at or below this batch the whole
# (sublane-padded) batch is staged in VMEM and the grid runs over neuron
# tiles only.
SMALL_BATCH_MAX = 8

# Per-backend VMEM capacity in bytes. CPU (interpret mode) has no hard cap,
# but uses the TPU budget so block choices transfer to the real target.
VMEM_BYTES = {"tpu": 16 * 2**20, "cpu": 16 * 2**20}
# Fraction of VMEM one grid step's working set may occupy (the rest is left
# for double buffering of the next blocks and compiler temporaries).
VMEM_USABLE_FRACTION = 0.5

_WORD = 4  # f32 values / int32 indices; bf16 inputs still accumulate in f32
_LANE_BITS = 7  # log2(LANE)


def default_interpret(backend: str | None = None) -> bool:
    """Interpret mode on the CPU only, where Mosaic cannot lower; a TPU
    always runs the compiled kernels."""
    return (backend or jax.default_backend()) == "cpu"


def vmem_budget_bytes(backend: str | None = None) -> int:
    """Usable per-kernel VMEM budget in bytes.

    ``REPRO_VMEM_CAP_BYTES`` overrides the per-backend cap (use it on parts
    whose VMEM differs from the 16 MiB v5e figure, or to mirror an explicit
    ``pltpu.CompilerParams(vmem_limit_bytes=...)``); the usable fraction
    still applies on top, preserving double-buffering headroom. A backend
    with no entry in ``VMEM_BYTES`` is an error: its budget is unknown.
    """
    env = os.environ.get("REPRO_VMEM_CAP_BYTES")
    if env:
        cap = int(env)
    else:
        backend = backend or jax.default_backend()
        if backend not in VMEM_BYTES:
            raise ValueError(f"no VMEM size known for backend {backend!r}; "
                             f"known: {sorted(VMEM_BYTES)} (or set "
                             "REPRO_VMEM_CAP_BYTES)")
        cap = VMEM_BYTES[backend]
    return int(cap * VMEM_USABLE_FRACTION)


def fwd_vmem_words(block_b: int, block_n: int, d_in: int, k: int) -> int:
    """Forward working set: x tile + (values + indices) tiles + the values
    tile widened to f32 + out tile."""
    return block_b * d_in + block_n * k * 3 + block_b * block_n


def dw_vmem_words(block_b: int, block_n: int, d_in: int, k: int) -> int:
    """dw working set: dy tile + x tile + indices tile + dw accumulator."""
    return block_b * block_n + block_b * d_in + 2 * block_n * k


def _ceil_to(v: int, m: int) -> int:
    return -(-v // m) * m


_BLOCK_B_CANDIDATES = (8, 16, 32, 64, 128, 256, 512)
_BLOCK_N_CANDIDATES = (128, 256, 512, 1024)


def _aligned_candidates(words_fn, b: int, d_in: int, n_out: int, k: int,
                        backend: str | None) -> list[tuple[int, int]]:
    """All 8x128-aligned (block_b, block_n) shapes whose ``words_fn`` working
    set fits the VMEM budget. Blocks larger than the padded problem dims are
    excluded (they only add padding work). Always returns at least one
    shape: the (8, 128) minimum is kept even if the budget formula rejects
    it, because ``d_in`` is structurally unblocked — a problem too large at
    minimum blocks needs a different kernel, not a smaller tile.
    """
    budget = vmem_budget_bytes(backend)
    bp = _ceil_to(max(b, 1), SUBLANE)
    np_ = _ceil_to(max(n_out, 1), LANE)
    out = []
    for bb in _BLOCK_B_CANDIDATES:
        if bb > bp and bb != SUBLANE:
            continue
        for bn in _BLOCK_N_CANDIDATES:
            if bn > np_ and bn != LANE:
                continue
            if words_fn(bb, bn, d_in, k) * _WORD <= budget:
                out.append((bb, bn))
    if not out:
        out.append((SUBLANE, LANE))
    return out


def block_candidates(b: int, d_in: int, n_out: int, k: int, *,
                     backend: str | None = None) -> list[tuple[int, int]]:
    """Forward-kernel candidates (see _aligned_candidates / fwd_vmem_words)."""
    return _aligned_candidates(fwd_vmem_words, b, d_in, n_out, k, backend)


def dw_block_candidates(b: int, d_in: int, n_out: int, k: int, *,
                        backend: str | None = None) -> list[tuple[int, int]]:
    """dw-kernel candidates (see _aligned_candidates / dw_vmem_words)."""
    return _aligned_candidates(dw_vmem_words, b, d_in, n_out, k, backend)


def _fit_block_b(words_fn, block_n: int, b: int, d_in: int, k: int, *,
                 backend: str | None = None, cap: int | None = None) -> int:
    """Largest aligned batch tile fitting ``words_fn``'s budget at a FORCED
    neuron tile (any ``block_n``, aligned or not). Floors at the 8-row
    minimum — a caller-forced neuron tile is honored even over budget."""
    budget = vmem_budget_bytes(backend)
    bp = _ceil_to(max(b, 1), SUBLANE)
    best = SUBLANE
    for bb in _BLOCK_B_CANDIDATES:
        if (bb > bp and bb != SUBLANE) or (cap is not None and bb > cap):
            continue
        if words_fn(bb, block_n, d_in, k) * _WORD <= budget:
            best = max(best, bb)
    return best


def _fit_block_n(words_fn, block_b: int, n_out: int, d_in: int, k: int, *,
                 backend: str | None = None, cap: int | None = None) -> int:
    """Mirror of _fit_block_b: largest aligned neuron tile fitting the
    budget at a FORCED batch tile, flooring at the 128-lane minimum."""
    budget = vmem_budget_bytes(backend)
    np_ = _ceil_to(max(n_out, 1), LANE)
    best = LANE
    for bn in _BLOCK_N_CANDIDATES:
        if (bn > np_ and bn != LANE) or (cap is not None and bn > cap):
            continue
        if words_fn(block_b, bn, d_in, k) * _WORD <= budget:
            best = max(best, bn)
    return best


def pick_default_blocks(cands: list[tuple[int, int]], b: int,
                        n_out: int) -> tuple[int, int]:
    """Default-block policy shared by every kernel family: the legacy
    128x128 when it is among ``cands``, otherwise the largest fitting
    candidate (closest to the target first, then raw area)."""
    target = (min(128, _ceil_to(max(b, 1), SUBLANE)),
              min(128, _ceil_to(max(n_out, 1), LANE)))
    if target in cands:
        return target
    return max(cands, key=lambda c: (min(c[0], target[0]) * min(c[1], target[1]),
                                     c[0] * c[1]))


def default_blocks(b: int, d_in: int, n_out: int, k: int, *,
                   backend: str | None = None) -> tuple[int, int]:
    """Untimed default block shape: the legacy 128x128 when it fits the VMEM
    budget, otherwise the largest fitting candidate (batch dim shrinks first
    — the ``B_blk * d_in`` x-tile term is what blows the budget at large
    ``d_in``). The timed search in repro.sparse.autotune refines this."""
    return pick_default_blocks(block_candidates(b, d_in, n_out, k,
                                                backend=backend), b, n_out)


def default_dw_blocks(b: int, d_in: int, n_out: int, k: int, *,
                      backend: str | None = None) -> tuple[int, int]:
    """Largest fitting dw block: stage as much batch per step as the budget
    allows (fewer accumulation passes over the output block), neuron tile at
    the legacy 128 when possible."""
    cands = dw_block_candidates(b, d_in, n_out, k, backend=backend)
    bn_target = min(128, _ceil_to(max(n_out, 1), LANE))
    with_bn = [c for c in cands if c[1] == bn_target] or cands
    return max(with_bn, key=lambda c: c[0])


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------


def _lane_tiles(n_blk: int) -> list[slice]:
    """The tile's neuron lanes in chunks of at most 128 (a chunk under 128
    arises only from a forced block_n below one vreg width)."""
    return [slice(o, min(o + LANE, n_blk)) for o in range(0, n_blk, LANE)]


def _gather_chunk(x_ref, idx: jax.Array) -> jax.Array:
    """``x[:, idx]`` for one (1, width <= 128) row of indices, as a
    (B_blk, width) f32 tile.

    Mosaic gathers along lanes only within one 128-lane vreg, with index,
    source and result of the same shape. So the gather walks the 128-lane
    chunks of ``x``: each chunk gathers at ``idx % 128`` and keeps the lanes
    whose index falls in that chunk. ``x_ref`` is f32 with ``d_in`` padded to
    a 128 multiple.
    """
    bb, d_pad = x_ref.shape
    width = idx.shape[1]
    if width < LANE:
        idx = jnp.pad(idx, ((0, 0), (0, LANE - width)))
    local = jnp.broadcast_to(jnp.bitwise_and(idx, LANE - 1), (bb, LANE))
    chunk = jnp.broadcast_to(jnp.right_shift(idx, _LANE_BITS), (bb, LANE))

    def from_chunk(c, g):
        xc = x_ref[:, pl.ds(pl.multiple_of(c * LANE, LANE), LANE)]
        hit = jnp.take_along_axis(xc, local, axis=1, mode="promise_in_bounds")
        return jnp.where(chunk == c, hit, g)

    g = jax.lax.fori_loop(0, d_pad // LANE, from_chunk,
                          jnp.zeros((bb, LANE), jnp.float32))
    return g if width == LANE else g[:, :width]


def _values_f32(wt_ref, scratch, round_to):
    """The tile's values as an f32 ref whose rows the slot loop reads.

    f32 values are read in place. Others (bf16 storage, int8/fp8 codes, or
    f32 rounded to a narrower compute dtype) are widened once per grid step
    into the (k, N_blk) f32 scratch: Mosaic loads one row of a packed
    (16- or 8-bit) tile only at a sublane-aligned offset."""
    if not scratch:
        return wt_ref
    v = wt_ref[...]
    if round_to is not None:
        v = v.astype(round_to)
    scratch[0][...] = v.astype(jnp.float32)
    return scratch[0]


def _gather_reduce(x_ref, wt, it_ref) -> jax.Array:
    """(B_blk, N_blk) f32 tile: ``out[b, n] = sum_j x[b, idx[j, n]] w[j, n]``.

    ``wt``/``it_ref`` hold the (k, N_blk) values (f32) and indices, so
    fan-in slot ``j`` of every neuron in the tile is one row; the
    k-reduction is an f32 running sum over those rows. Each slot loads its
    rows whole and slices them per 128-lane chunk: Mosaic refuses a
    dynamic-row load at a lane offset inside the tile.
    """
    k, n_blk = it_ref.shape
    lanes = _lane_tiles(n_blk)

    def slot(j, accs):
        idx = it_ref[pl.ds(j, 1), :]
        w = wt[pl.ds(j, 1), :]
        return tuple(acc + _gather_chunk(x_ref, idx[:, ln]) * w[:, ln]
                     for acc, ln in zip(accs, lanes))

    zeros = tuple(jnp.zeros((x_ref.shape[0], ln.stop - ln.start), jnp.float32)
                  for ln in lanes)
    accs = jax.lax.fori_loop(0, k, slot, zeros)
    return accs[0] if len(accs) == 1 else jnp.concatenate(accs, axis=1)


def _fwd_kernel(x_ref, wt_ref, it_ref, out_ref, *scratch, round_to):
    """One (B_blk, N_blk) output tile.

    x_ref  : (B_blk, d_pad)  VMEM f32
    wt_ref : (k, N_blk)      VMEM, values as stored (see _values_f32)
    it_ref : (k, N_blk)      VMEM int32
    out_ref: (B_blk, N_blk)  VMEM
    """
    wt = _values_f32(wt_ref, scratch, round_to)
    out_ref[...] = _gather_reduce(x_ref, wt, it_ref).astype(out_ref.dtype)


def _fwd_scaled_kernel(x_ref, wt_ref, it_ref, scale_ref, out_ref, *scratch):
    """Quantized variant of ``_fwd_kernel``: ``wt_ref`` holds the int8/fp8
    codes and ``scale_ref`` a (1, N_blk) tile of per-neuron f32 scales. The
    scale multiply is applied AFTER the k-reduction — exact (the scale is
    constant over a neuron's fan-in) and one multiply per output element
    instead of one per weight."""
    acc = _gather_reduce(x_ref, _values_f32(wt_ref, scratch, None), it_ref)
    out_ref[...] = (acc * scale_ref[...]).astype(out_ref.dtype)


def _dw_kernel(dy_ref, x_ref, it_ref, dwt_ref):
    """dw tile, transposed: dwt[j, n] = sum_b dy[b, n] * x[b, idx[j, n]].

    dy_ref : (B_blk, N_blk), x_ref : (B_blk, d_pad) f32, it_ref : (k, N_blk).
    Grid is (neuron tiles, batch tiles) with batch innermost: the output
    block stays resident while batch tiles accumulate into it, so the full
    batch is never staged in VMEM at once (see dw_vmem_words).
    """
    @pl.when(pl.program_id(1) == 0)
    def _init():
        dwt_ref[...] = jnp.zeros_like(dwt_ref)

    k, n_blk = it_ref.shape
    lanes = _lane_tiles(n_blk)
    dys = [dy_ref[:, ln].astype(jnp.float32) for ln in lanes]

    def slot(j, carry):
        idx = it_ref[pl.ds(j, 1), :]
        parts = [jnp.sum(dy * _gather_chunk(x_ref, idx[:, ln]), axis=0,
                         keepdims=True) for dy, ln in zip(dys, lanes)]
        dwt_ref[pl.ds(j, 1), :] += (parts[0] if len(parts) == 1
                                    else jnp.concatenate(parts, axis=1))
        return carry

    jax.lax.fori_loop(0, k, slot, 0)


# ---------------------------------------------------------------------------
# pallas_call wrappers
# ---------------------------------------------------------------------------


def pad_x(x: jax.Array, bp: int) -> jax.Array:
    """``x`` in the gather kernels' layout: widened to f32, rows padded to
    ``bp`` and ``d_in`` rounded up to whole 128-lane chunks."""
    b, d_in = x.shape
    return jnp.pad(x.astype(jnp.float32),
                   ((0, bp - b), (0, _ceil_to(d_in, LANE) - d_in)))


def round_dtype(x_dtype, values_dtype):
    """What float values round to before the f32 product: the activations'
    dtype when it is narrower than the stored values (the reference's
    ``values.astype(x.dtype)``), else nothing."""
    if (jnp.issubdtype(values_dtype, jnp.floating)
            and jnp.dtype(x_dtype).itemsize < jnp.dtype(values_dtype).itemsize):
        return jnp.dtype(x_dtype)
    return None


def values_scratch(values_dtype, round_to, k: int, block_n: int) -> list:
    """The f32 VMEM buffer ``_values_f32`` widens a values tile into; none
    when the stored values are f32 and kept as they are."""
    if jnp.dtype(values_dtype) == jnp.float32 and round_to is None:
        return []
    return [pltpu.VMEM((k, block_n), jnp.float32)]


@functools.partial(jax.jit, static_argnames=("block_b", "block_n", "interpret"))
def _fwd_tiled(x, values, indices, scales=None, *, block_b: int, block_n: int,
               interpret: bool):
    """Forward: grid over (batch tiles, neuron tiles). ``scales``
    (per-neuron f32, quantized values) adds a (1, block_n) tile and routes
    to the dequant-fused kernel. Values and indices enter in their stored
    dtype, viewed (k, n_out); a last neuron tile may overhang ``n_out``."""
    b = x.shape[0]
    n_out, k = values.shape
    bp = _ceil_to(max(b, 1), block_b)
    xp = pad_x(x, bp)
    round_to = None if scales is not None else round_dtype(x.dtype,
                                                           values.dtype)

    in_specs = [
        pl.BlockSpec((block_b, xp.shape[1]), lambda i, j: (i, 0)),
        pl.BlockSpec((k, block_n), lambda i, j: (0, j)),
        pl.BlockSpec((k, block_n), lambda i, j: (0, j)),
    ]
    operands = [xp, values.T, indices.astype(jnp.int32).T]
    if scales is None:
        kernel = functools.partial(_fwd_kernel, round_to=round_to)
    else:
        in_specs.append(pl.BlockSpec((1, block_n), lambda i, j: (0, j)))
        operands.append(scales.astype(jnp.float32).reshape(1, n_out))
        kernel = _fwd_scaled_kernel

    out = pl.pallas_call(
        kernel,
        grid=(bp // block_b, pl.cdiv(n_out, block_n)),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((block_b, block_n), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, n_out), x.dtype),
        scratch_shapes=values_scratch(values.dtype, round_to, k, block_n),
        interpret=interpret,
        name="condensed_matmul",
    )(*operands)
    return out[:b]


def condensed_matmul(
    x: jax.Array,
    values: jax.Array,
    indices: jax.Array,
    *,
    scales: jax.Array | None = None,
    block_b: int | None = None,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Forward condensed matmul via pallas_call. Shapes as in ref.py.

    ``block_b=None`` auto-selects: decode shapes (B <= SMALL_BATCH_MAX) go to
    the decode-specialized variant, larger batches get the VMEM-budget
    default (see default_blocks; repro.sparse.autotune supplies timed
    choices). ``interpret=None`` resolves from the backend (CPU only).
    Explicit ``block_b`` forces the general tiled kernel.

    ``scales`` (shape (n_out,), f32) marks ``values`` as quantized codes
    (int8/fp8); dequantization fuses into the kernel epilogue. Float
    ``values`` may be stored wider than ``x``: they round to ``x.dtype`` in
    the kernel, as the reference's cast does.
    """
    b, d_in = x.shape
    n_out, k = values.shape
    if interpret is None:
        interpret = default_interpret()
    if block_b is None and b <= SMALL_BATCH_MAX:
        return condensed_matmul_decode(x, values, indices, scales=scales,
                                       block_n=block_n, interpret=interpret)
    if block_b is None and block_n is None:
        block_b, block_n = default_blocks(b, d_in, n_out, k)
    elif block_b is None:
        # a forced neuron tile re-sizes the batch tile against the SAME
        # budget (a 128-sized default could overflow VMEM at large block_n)
        block_b = _fit_block_b(fwd_vmem_words, block_n, b, d_in, k, cap=128)
    elif block_n is None:
        block_n = _fit_block_n(fwd_vmem_words, block_b, n_out, d_in, k,
                               cap=128)
    return _fwd_tiled(x, values, indices, scales, block_b=block_b,
                      block_n=block_n, interpret=interpret)


def condensed_matmul_decode(
    x: jax.Array,
    values: jax.Array,
    indices: jax.Array,
    *,
    scales: jax.Array | None = None,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Decode-specialized condensed matmul (batch staged whole).

    Bit-identical to the general kernel: the per-row reduction over k is
    independent of how the batch axis is padded or tiled. Intended for
    B <= SMALL_BATCH_MAX but correct for any batch that fits VMEM."""
    b, d_in = x.shape
    n_out, k = values.shape
    if interpret is None:
        interpret = default_interpret()
    if block_n is None:
        _, block_n = default_blocks(min(b, SMALL_BATCH_MAX), d_in, n_out, k)
    return _fwd_tiled(x, values, indices, scales,
                      block_b=_ceil_to(max(b, 1), SUBLANE), block_n=block_n,
                      interpret=interpret)


@functools.partial(jax.jit, static_argnames=("block_b", "block_n", "interpret"))
def _dw_tiled(dy, x, indices, *, block_b: int, block_n: int, interpret: bool):
    b = x.shape[0]
    n_out, k = indices.shape
    bp = _ceil_to(max(b, 1), block_b)
    # padded batch rows: zero cotangents times the zero rows of pad_x
    dyp = jnp.pad(dy, ((0, bp - b), (0, 0)))
    xp = pad_x(x, bp)

    # batch tiles innermost (last grid dim iterates fastest): the (k, block_n)
    # output block stays resident across the accumulation
    dwt = pl.pallas_call(
        _dw_kernel,
        grid=(pl.cdiv(n_out, block_n), bp // block_b),
        in_specs=[
            pl.BlockSpec((block_b, block_n), lambda j, i: (i, j)),
            pl.BlockSpec((block_b, xp.shape[1]), lambda j, i: (i, 0)),
            pl.BlockSpec((k, block_n), lambda j, i: (0, j)),
        ],
        out_specs=pl.BlockSpec((k, block_n), lambda j, i: (0, j)),
        out_shape=jax.ShapeDtypeStruct((k, n_out), jnp.float32),
        interpret=interpret,
        name="condensed_matmul_dw",
    )(dyp, xp, indices.astype(jnp.int32).T)
    return dwt.T.astype(values_dtype(dy))


def condensed_matmul_dw(
    dy: jax.Array,
    x: jax.Array,
    indices: jax.Array,
    *,
    block_b: int | None = None,
    block_n: int | None = None,
    interpret: bool | None = None,
) -> jax.Array:
    """Backward-wrt-values kernel. dy: (B, n_out), x: (B, d_in) -> (n_out, k).

    Blocked over batch tiles (``block_b``), accumulating into the output
    block, so large-batch training shapes never stage the full batch in
    VMEM; the working set per grid step is ``dw_vmem_words`` words. Defaults
    stage the largest batch tile the VMEM budget allows.
    """
    b, d_in = x.shape
    n_out, k = indices.shape
    if interpret is None:
        interpret = default_interpret()
    if block_b is None and block_n is None:
        block_b, block_n = default_dw_blocks(b, d_in, n_out, k)
    elif block_b is None:
        # size the batch tile against the dw budget AT the forced neuron
        # tile — default_dw_blocks assumes a 128-wide tile and its block_b
        # could overflow VMEM when combined with a larger caller block_n
        block_b = _fit_block_b(dw_vmem_words, block_n, b, d_in, k)
    elif block_n is None:
        block_n = _fit_block_n(dw_vmem_words, block_b, n_out, d_in, k,
                               cap=128)
    return _dw_tiled(dy, x, indices, block_b=block_b, block_n=block_n,
                     interpret=interpret)


def values_dtype(dy: jax.Array):
    # Gradients accumulate in f32 regardless of activation dtype.
    return jnp.float32 if dy.dtype in (jnp.bfloat16, jnp.float16) else dy.dtype
