"""First-class sparse serving formats: one protocol, four representations.

The paper's serving story (Sec. 4.4, Fig. 4) is that ONE trained constant
fan-in topology can be *executed* under several storage/compute
representations, and which one wins is a property of the request shape and
the hardware balance. This module makes each representation a registered
pytree dataclass with a single shared protocol, replacing the untyped
``{"values": ..., "indices": ...}`` dict leaves that every consumer used to
re-interpret with its own key-sniffing conventions.

Mapping to PAPER.md Fig. 4 (serving-time representations of an SRigL mask):

* ``MaskedDense``          — the training layout: dense weight + bool mask,
                             dense MXU matmul. Fig. 4's "dense/masked"
                             baseline point; wins back at large batch.
* ``StructuredFanIn``      — Fig. 4 "structured": ablated output neurons are
                             dropped, surviving columns stay dense and run
                             through the column-gathered Pallas kernel
                             (``active_index``; bytes/FLOPs scale with the
                             active fraction). Exact only for ablation-only
                             masks.
* ``Condensed``            — Fig. 4 "condensed": the constant fan-in gather
                             layout (Alg. 1). Weight reads shrink to
                             n_out*k entries; wins the bandwidth-bound
                             decode shapes.
* ``CondensedOverActive``  — Fig. 4's combined point: ablated neurons are
                             dropped FIRST, then the survivors are
                             condensed. Exact for any mask; the byte/FLOP
                             saving over plain condensed is the ablated
                             fraction.

Protocol (every format implements all of it):

* ``apply(x, w)``                    — execute the sparse linear. ``w`` is
                                       the live dense weight (read by the
                                       masked/structured formats, ignored by
                                       the condensed family).
* ``export_from_dense(w, mask, stats)`` (classmethod) — build the format
                                       from a trained (weight, mask) pair.
* ``cost(batch, profile)``           — estimated seconds per serving step
                                       under ``profile`` (the plan cost
                                       model); ``estimate_cost`` is the
                                       allocation-free classmethod variant
                                       priced from a ``FormatSpec``.
* ``tuning_key(batch, ...)``         — the autotune-cache key this format's
                                       kernel dispatch looks up (None for
                                       formats with no tunable kernel).
* ``donate_refresh(w, mask, stats)`` — in-place re-export: rebuilds the
                                       format with ``self``'s old device
                                       buffers DONATED whenever the new
                                       arrays have matching avals (a live
                                       serving job never holds two copies).
* ``refresh_values(w, mask)``        — cheap values-only refresh under
                                       unchanged topology (indices reused
                                       verbatim; no-op for formats that
                                       read the live weights).

Formats are pytree nodes: their array fields are traced leaves (they flow
through ``jit``/``lax.scan``/``device_put``/donation like any array) and
their static fields ride along as hashable aux data. ``from_legacy_leaf``
upgrades the pre-redesign dict leaves (deprecation shim), so existing
checkpoints and serialized serving trees keep loading.
"""
from __future__ import annotations

import dataclasses
import functools
import typing
import warnings

import jax
import jax.numpy as jnp

from repro.core import topology
from repro.core.srigl import apply_mask_for_forward
from repro.kernels import ops
from repro.kernels.structured_matmul import padded_active_count


class ExportStats(typing.NamedTuple):
    """Realized per-stack structure, measured from the trained masks."""
    k: int                  # max realized fan-in over all columns/replicas
    max_active: int         # max active (non-ablated) neurons over replicas
    active_fraction: float  # mean fraction of active neurons
    # min realized fan-in over ACTIVE columns (columns with >= 1 non-zero);
    # min_fan_in == d_in means every surviving column is fully dense — the
    # ablation-ONLY regime where the structured column-drop representation
    # is exact. Defaults to 0 ("unknown / not ablation-only") so stats built
    # by older call sites never enable structured by accident.
    min_fan_in: int = 0


@dataclasses.dataclass(frozen=True)
class FormatSpec:
    """Static geometry a format is priced and cache-keyed from (no arrays).

    Built either from a registry ``SparseStack`` + realized ``ExportStats``
    (``spec_for_stack``) or from a live format instance (``fmt.spec()``) —
    the allocation-free half of the protocol, used by the plan cost model
    and the autotune key derivation before any export has happened.

    ``values_dtype`` is the canonical short name of the exported values
    storage dtype (``"int8"``/``"fp8"`` for the quantized formats, None for
    "same as ``itemsize``'s dtype") — the pricing methods read the REAL byte
    width from it so ``--path auto`` re-derives its crossovers honestly
    under quantization.
    """
    d_in: int
    d_out: int
    n_replicas: int
    itemsize: int           # serving dtype bytes for values/weights
    k: int                  # constant fan-in
    max_active: int         # exported row count for condensed-over-active
    active_fraction: float  # mean active-neuron fraction
    values_dtype: str | None = None  # canonical name; None = itemsize's dtype
    tp: int = 1             # neuron-axis tensor-parallel shard count


# ---------------------------------------------------------------------------
# quantized values: canonical dtype names + per-neuron symmetric scales
# ---------------------------------------------------------------------------

# canonical names accepted by quantize_spec / --values-dtype. fp8 resolves to
# e4m3 (the inference-weight variant) where this jax build carries it.
_FP8_DTYPE = getattr(jnp, "float8_e4m3fn", None)
VALUES_DTYPES: dict[str, typing.Any] = {
    "f32": jnp.float32, "bf16": jnp.bfloat16, "int8": jnp.int8,
    **({"fp8": _FP8_DTYPE} if _FP8_DTYPE is not None else {}),
}
QUANTIZED_DTYPES = ("int8", "fp8")
# symmetric per-neuron scale maps the row's absmax onto the code's top value
_QMAX = {"int8": 127.0, "fp8": 448.0}  # e4m3 finite max = 448


def resolve_quantize_spec(spec) -> str | None:
    """Normalize a quantize spec (canonical name / dtype / None) to a
    canonical name, validating backend support. ``"f32"``/None mean "no
    quantization" (the export keeps float values, no scales)."""
    if spec is None or spec == "f32":
        return None
    if isinstance(spec, str):
        name = spec
    else:
        dt = jnp.dtype(spec)
        by_dtype = {jnp.dtype(v): k for k, v in VALUES_DTYPES.items()}
        name = by_dtype.get(dt, dt.name)
    if name in ("f32", "float32"):
        return None
    if name == "fp8" and _FP8_DTYPE is None:
        raise ValueError("fp8 values need a jax build with float8_e4m3fn; "
                         "this one has none — use int8 instead")
    if name not in VALUES_DTYPES:
        raise ValueError(f"unknown values dtype {spec!r}; expected one of "
                         f"{sorted(VALUES_DTYPES)}")
    return name


def values_itemsize(spec: FormatSpec) -> int:
    """Byte width of one stored value under ``spec`` (the real streamed
    width, not the compute dtype's)."""
    if spec.values_dtype is None:
        return spec.itemsize
    return jnp.dtype(VALUES_DTYPES[spec.values_dtype]).itemsize


def quantize_values(values, name: str, *, axis: int = -1):
    """Per-neuron symmetric quantization of a float values array.

    ``axis`` is the within-neuron axis reduced for the scale (fan-in ``k``
    for the condensed layouts, ``d_in`` for the structured gathered panel).
    Returns ``(q, scales)`` with ``scales = absmax/qmax`` as float32 and
    ``q ~ values/scales`` in the target dtype. All-zero rows (and the exact-
    zero padding slots the exports guarantee) quantize to exact 0 under a
    scale of 1, so dequantization reproduces their zeros bit-exactly.
    """
    name = typing.cast(str, resolve_quantize_spec(name))
    if name not in QUANTIZED_DTYPES:
        raise ValueError(f"quantize_values needs one of {QUANTIZED_DTYPES}, "
                         f"got {name!r}")
    v = values.astype(jnp.float32)
    amax = jnp.max(jnp.abs(v), axis=axis, keepdims=True)
    scales = jnp.where(amax > 0, amax / _QMAX[name], 1.0).astype(jnp.float32)
    scaled = v / scales
    if name == "int8":
        q = jnp.clip(jnp.round(scaled), -127.0, 127.0).astype(jnp.int8)
    else:
        q = scaled.astype(VALUES_DTYPES[name])
    return q, jnp.squeeze(scales, axis=axis)


def dequantize_values(q, scales, *, axis: int = -1, dtype=jnp.float32):
    """Inverse of ``quantize_values``: broadcast the per-neuron scale back
    over ``axis`` (the reference dequantization the kernels fuse)."""
    return (q.astype(jnp.float32)
            * jnp.expand_dims(scales.astype(jnp.float32), axis)).astype(dtype)


def spec_for_stack(stack, stats: ExportStats, itemsize: int,
                   values_dtype: str | None = None, tp: int = 1) -> FormatSpec:
    """``stack`` is duck-typed (registry.SparseStack or any object with
    d_in/d_out; n_replicas defaults to 1 — benchmarks price bare shapes)."""
    return FormatSpec(d_in=stack.d_in, d_out=stack.d_out,
                      n_replicas=getattr(stack, "n_replicas", 1),
                      itemsize=itemsize,
                      k=max(stats.k, 1), max_active=max(stats.max_active, 1),
                      active_fraction=min(max(stats.active_fraction, 0.0), 1.0),
                      values_dtype=resolve_quantize_spec(values_dtype),
                      tp=max(int(tp), 1))


def shape_tuning_key(d_in: int, n_out: int, k: int, batch: int, *,
                     backend: str | None = None, itemsize: int = 4,
                     kind: str = "condensed",
                     scatter_width: int | None = None,
                     values_dtype: str | None = None) -> str:
    """Canonical autotune-cache key for a sparse kernel dispatch shape.

    Single definition shared by the formats' ``tuning_key`` methods, by
    ``repro.sparse.autotune`` (which persists entries under it) and by
    ``repro.kernels.ops`` (which looks blocks up at trace time) — the three
    can never drift. Batch is bucketed (``autotune.batch_bucket``) so a
    tuned entry serves every batch in its bucket, and the SAME buckets key
    the serving engine's request groups.

    ``kind`` separates the key spaces of the three kernels (entries are only
    valid for the kernel they were timed on):

    * ``"condensed"`` — the plain condensed gather; key layout unchanged
      from earlier cache versions.
    * ``"structured"`` — the column-gathered structured matmul; ``n_out`` is
      the padded active-column count, ``k`` is 0 (the contraction width is
      ``d_in`` itself) and ``scatter_width`` is the dense output width the
      fused epilogue scatters into (part of the kernel's VMEM geometry).
    * ``"coa"`` — the fused condensed-over-active kernel; ``n_out``/``k``
      are the surviving-row condensed arrays' dims and ``scatter_width`` is
      again the dense output width.

    ``values_dtype`` (a canonical name from ``VALUES_DTYPES``) distinguishes
    quantized key spaces: int8 and fp8 both store 1 byte per value, so the
    plain ``w{bits}`` width component cannot tell them apart — quantized
    dispatches key as ``w<name>`` (e.g. ``wint8``) instead. Float dtypes
    keep the byte-identical legacy ``w{bits}`` layout so every existing
    cache entry stays valid.
    """
    from repro.sparse import autotune as AT  # lazy: autotune is optional at import
    backend = backend or jax.default_backend()
    vd = resolve_quantize_spec(values_dtype)
    width = f"w{vd}" if vd in QUANTIZED_DTYPES else f"w{itemsize * 8}"
    key = (f"{backend}/{width}/d{d_in}/n{n_out}/k{k}"
           f"/b{AT.batch_bucket(batch)}")
    if kind != "condensed":
        key += f"/{kind}-o{scatter_width}"
    return key


def _gather_rate(profile, batch: int) -> float:
    """Batch-dependent gather throughput: profiles calibrated at two points
    (see plan.HardwareProfile.gather_rate) expose the activation-traffic
    cache cliff; single-rate profiles fall back to their scalar rate."""
    fn = getattr(profile, "gather_rate", None)
    if callable(fn):
        return fn(batch)
    return profile.gather_flops_per_s


def _map_lead(fn, n_lead: int):
    """``fn`` over ``n_lead`` leading (stack) axes: ``lax.map`` over the
    first, one slab at a time, vmapped over the rest. An export sorts each
    layer's (d_in, d_out) mask; vmapped over all of a full-width stack's
    layers at once its temporaries alone outgrow one chip's HBM."""
    if n_lead == 0:
        return fn
    for _ in range(n_lead - 1):
        fn = jax.vmap(fn)
    return lambda *xs: jax.lax.map(lambda args: fn(*args), xs)


def _realized_stats(mask) -> ExportStats:
    """Host-syncing fallback when the caller has no precomputed stats."""
    d_in = mask.shape[-2]
    nnz = jnp.sum(mask.astype(jnp.int32), axis=-2)
    act = jnp.any(mask, axis=-2)
    k, a, frac, mk = jax.device_get((
        jnp.max(nnz), jnp.max(jnp.sum(act.astype(jnp.int32), axis=-1)),
        jnp.mean(act.astype(jnp.float32)),
        jnp.min(jnp.where(nnz > 0, nnz, d_in))))
    return ExportStats(k=int(k), max_active=int(a), active_fraction=float(frac),
                       min_fan_in=int(mk))


def active_index_from_bools(neuron_active: jax.Array, a_pad: int) -> jax.Array:
    """Surviving-column index vector for the structured kernel, from the
    per-neuron active bools (lead dims vmapped).

    Returns (lead..., a_pad) int32: the ids of the active columns in
    increasing order, padded with the out-of-range sentinel ``d_out`` — the
    fused scatter epilogue drops sentinel slots exactly, so ``a_pad`` only
    needs to be an upper bound on each replica's realized active count
    (``padded_active_count`` rounds it to the 128-lane tile).
    """
    d_out = neuron_active.shape[-1]
    n = min(a_pad, d_out)

    def fn(act):
        order = jnp.argsort(~act, stable=True).astype(jnp.int32)
        oi = jnp.where(act[order[:n]], order[:n], d_out)
        return jnp.pad(oi, (0, a_pad - n),
                       constant_values=d_out).astype(jnp.int32)

    return _map_lead(fn, neuron_active.ndim - 1)(neuron_active)


def active_index_from_mask(mask: jax.Array, a_pad: int) -> jax.Array:
    """``active_index_from_bools`` of the mask's column-activity bools."""
    return active_index_from_bools(jnp.any(mask, axis=-2), a_pad)


# ---------------------------------------------------------------------------
# tensor-parallel (shard-blocked) layout helpers
#
# A TP export keeps every array at its GLOBAL shape but reorganizes the
# neuron/active-row axis into ``tp`` contiguous blocks, one per model-axis
# shard: values/indices rows are grouped by block and out_index/active_index
# entries are rebased to the block-LOCAL output range [0, d_out // tp) with
# the local sentinel ``d_out // tp`` marking padding. Sharding that axis over
# 'model' then gives each device exactly its own block, every gather stays
# shard-local against the replicated activation, and the constant fan-in
# guarantees the shards' work is exactly balanced (the property CSR lacks).
# ---------------------------------------------------------------------------


def _check_tp_shards(d_out: int, tp: int) -> int:
    tp = max(int(tp), 1)
    if tp > 1 and d_out % tp != 0:
        raise ValueError(f"tp_shards={tp} must divide the output width "
                         f"d_out={d_out} (neuron-axis blocks must be equal)")
    return tp


def _rebased_global_index(local_idx: jax.Array, tp: int,
                          d_out: int) -> jax.Array:
    """Map a block-LOCAL index vector (sentinel ``d_out // tp``) back to
    GLOBAL output positions (sentinel ``d_out``) — used wherever a TP
    instance must address the dense weight (refresh regathers) or reuse a
    global-layout program."""
    a_tp = local_idx.shape[-1] // tp
    wloc = d_out // tp
    off = (jnp.arange(local_idx.shape[-1], dtype=local_idx.dtype)
           // a_tp) * wloc
    return jnp.where(local_idx < wloc, local_idx + off,
                     d_out).astype(local_idx.dtype)


def _per_shard_active_bound(mask, tp: int) -> int:
    """Max active-neuron count over any tp-block of the output axis (ONE
    host sync — exports are host-driven, same as ``_realized_stats``)."""
    act = jnp.any(mask, axis=-2)
    wloc = act.shape[-1] // tp
    blocks = act.reshape(*act.shape[:-1], tp, wloc)
    n = jnp.max(jnp.sum(blocks.astype(jnp.int32), axis=-1))
    return max(int(jax.device_get(n)), 1)


# ---------------------------------------------------------------------------
# base class
# ---------------------------------------------------------------------------


class SparseFormat:
    """Base for the four serving formats (see module docstring).

    Subclasses are frozen dataclasses declaring ``_array_fields`` (pytree
    leaves) and ``_static_fields`` (hashable aux data); registration happens
    via ``_register``. Legacy dict-style access (``fmt["values"]``,
    ``"out_index" in fmt``) is kept as a migration convenience — new code
    should use the attributes.
    """
    format_name: typing.ClassVar[str]
    _array_fields: typing.ClassVar[tuple[str, ...]]
    _static_fields: typing.ClassVar[tuple[str, ...]] = ()

    # -- pytree protocol ----------------------------------------------------
    def tree_flatten(self):
        return (tuple(getattr(self, f) for f in self._array_fields),
                tuple(getattr(self, f) for f in self._static_fields))

    @classmethod
    def tree_unflatten(cls, aux, children):
        kw = dict(zip(cls._array_fields, children))
        kw.update(zip(cls._static_fields, aux))
        return cls(**kw)

    # -- legacy dict-leaf compatibility ------------------------------------
    def __getitem__(self, key: str):
        if key in self._array_fields:
            return getattr(self, key)
        raise KeyError(key)

    def __contains__(self, key: str) -> bool:
        return key in self._array_fields

    def to_legacy_dict(self) -> dict:
        """The pre-redesign dict leaf this format replaces. ``None`` fields
        (optional fields the instance does not carry, e.g. unquantized
        ``scales``) are omitted — the legacy layouts never had them."""
        return {f: getattr(self, f) for f in self._array_fields
                if getattr(self, f) is not None}

    def map_arrays_with_names(self, fn):
        """Rebuild with each array field replaced by ``fn(name, value)`` —
        used by sharding/checkpoint code that walks trees by path. ``None``
        fields (legacy instances predating an optional field) pass through."""
        return dataclasses.replace(
            self, **{f: (None if getattr(self, f) is None
                         else fn(f, getattr(self, f)))
                     for f in self._array_fields})

    # -- protocol (subclass responsibilities) -------------------------------
    def apply(self, x: jax.Array, w: jax.Array | None = None) -> jax.Array:
        raise NotImplementedError

    @classmethod
    def export_from_dense(cls, w, mask, stats: ExportStats | None = None, *,
                          quantize_spec=None):
        raise NotImplementedError

    def spec(self) -> FormatSpec:
        raise NotImplementedError

    def cost(self, batch: int, profile) -> float:
        """Estimated seconds per serving step for THIS exported instance."""
        return self.estimate_cost(self.spec(), batch, profile)

    @classmethod
    def estimate_cost(cls, spec: FormatSpec, batch: int, profile) -> float:
        raise NotImplementedError

    @classmethod
    def estimate_weight_bytes(cls, spec: FormatSpec) -> int:
        """Per-step weight-side HBM traffic this format actually reads."""
        raise NotImplementedError

    @classmethod
    def estimate_values_bytes(cls, spec: FormatSpec) -> int:
        """The VALUE stream alone (weights + per-neuron scales, excluding
        index/topology arrays both a float and a quantized export of the
        same mask share) — the bytes quantization actually shrinks."""
        return cls.estimate_weight_bytes(spec)

    # -- tensor-parallel pricing (collective-aware cost model) --------------
    @classmethod
    def shard_spec(cls, spec: FormatSpec, tp: int) -> FormatSpec:
        """The per-shard geometry a ``tp``-way neuron partition executes:
        the output width and surviving-row bound shrink by ``1/tp``
        (max_active via ceil — the even-spread approximation the export
        realizes exactly for plain condensed and approximately for the
        ablation formats), fan-in and replica count stay global (every
        shard reads the full replicated activation)."""
        tp = max(int(tp), 1)
        if tp == 1:
            return spec
        return dataclasses.replace(
            spec, d_out=max(spec.d_out // tp, 1),
            max_active=max(-(-spec.max_active // tp), 1), tp=1)

    @classmethod
    def estimate_collective(cls, spec: FormatSpec, batch: int, profile,
                            tp: int) -> float:
        """Seconds for the per-layer output all-gather a ``tp``-way neuron
        partition pays: each device ring-exchanges the other shards' (B,
        d_out/tp) output blocks — ``(tp-1)/tp`` of the replicated activation
        — at the profile's measured interconnect rate."""
        tp = max(int(tp), 1)
        if tp <= 1:
            return 0.0
        b = max(int(batch), 1)
        bytes_ = (b * spec.n_replicas * spec.d_out * spec.itemsize
                  * (tp - 1) / tp)
        return bytes_ / profile.ici_bytes_per_s

    @classmethod
    def estimate_cost_sharded(cls, spec: FormatSpec, batch: int, profile,
                              tp: int) -> float:
        """Estimated seconds per serving step under a ``tp``-way neuron
        partition: the per-shard execution (1/tp of the weight stream and
        gather work — the constant fan-in keeps shards exactly balanced)
        PLUS the output all-gather. ``--path auto`` compares this against
        ``estimate_cost`` (replicate, pay full HBM) so the shard-vs-
        replicate crossover comes out of the cost model, not a flag."""
        tp = max(int(tp), 1)
        if tp <= 1:
            return cls.estimate_cost(spec, batch, profile)
        return (cls.estimate_cost(cls.shard_spec(spec, tp), batch, profile)
                + cls.estimate_collective(spec, batch, profile, tp))

    def tuning_key(self, batch: int, *, backend: str | None = None) -> str | None:
        """Autotune-cache key for this instance's kernel dispatch (None when
        the format has no tunable kernel)."""
        return None

    @classmethod
    def spec_tuning_key(cls, spec: FormatSpec, batch: int, *,
                        backend: str | None = None) -> str | None:
        return None

    @classmethod
    def abstract(cls, lead: tuple[int, ...], d_in: int, d_out: int, k: int,
                 dtype) -> "SparseFormat":
        """ShapeDtypeStruct-leaved instance (dry-run / compile-only)."""
        raise NotImplementedError

    def donate_refresh(self, w, mask, stats: ExportStats | None = None, *,
                       donate: bool = True) -> "SparseFormat":
        """Full re-export from (w, mask), reusing ``self``'s device buffers
        when the new arrays' avals match. CAUTION: with ``donate=True`` and
        matching avals, ``self``'s arrays are invalidated."""
        return type(self).export_from_dense(w, mask, stats)

    def refresh_values(self, w, mask, *, donate: bool = True) -> "SparseFormat":
        """Values-only refresh under unchanged topology (no-op for formats
        that read the live weights at execution time)."""
        return self

    def rebuild_missing(self, missing: frozenset) -> "SparseFormat":
        """Recompute array fields an older checkpoint archive did not carry
        (``missing``: field names the restore found no arrays for). Default:
        keep the template's values. Overridden where a derived field must
        stay consistent with restored ones."""
        return self

    def restore_finalize(self) -> "SparseFormat":
        """Reconcile restored arrays with the template's declared storage
        dtype. Checkpoint restore keeps each array at the ARCHIVE's dtype,
        so a pre-quantization archive restored into a quantized template
        arrives with float values (re-quantize), and a quantized archive
        restored into a float template arrives with int8/fp8 values plus
        adopted scales (dequantize). Default: nothing to reconcile."""
        return self

    def adopt_arrays(self, new: dict, *, donate: bool = True):
        """Rebuild with the array fields named in ``new`` replaced by the
        given (host or device) arrays, donating each matching old buffer
        (see :func:`adopt_array`). The sync-subscriber apply path: the new
        bytes were exported remotely, so this is replacement, not
        re-export."""
        unknown = set(new) - set(self._array_fields)
        if unknown:
            raise ValueError(f"{type(self).__name__} has no array fields "
                             f"{sorted(unknown)}")
        return dataclasses.replace(
            self, **{f: adopt_array(v, getattr(self, f), donate=donate)
                     for f, v in new.items()})


def _register(cls):
    jax.tree_util.register_pytree_node_class(cls)
    return cls


# ---------------------------------------------------------------------------
# buffer adoption: write an externally-computed array over a live one
#
# The sync subscriber (repro.sync) hands the engine host arrays that were
# exported on the TRAINER -- there is no (w, mask) pair to re-export from,
# so the donated-refresh programs above don't apply. Adoption is the
# degenerate donated program: identity over the new array with the old
# buffer donated, so XLA writes the incoming bytes into the replica's
# existing allocation and the old jax.Array is invalidated at dispatch --
# zero weight-memory doubling, same guarantee as donate_refresh.
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, donate_argnums=(1,), keep_unused=True)
def _adopt_donated(new, old):
    return new


def adopt_array(new, old=None, *, donate: bool = True):
    """Move ``new`` (host or device) onto device, donating ``old``'s buffer
    when it is a live matching-aval jax.Array. Falls back to a plain
    transfer when shapes/dtypes differ (a topology delta that changed k or
    the active-row count) or donation is off."""
    arr = jnp.asarray(new)
    if (donate and old is not None and isinstance(old, jax.Array)
            and not old.is_deleted()
            and old.shape == arr.shape and old.dtype == arr.dtype):
        return _adopt_donated(arr, old)
    return arr


# ---------------------------------------------------------------------------
# jitted donated re-export programs (shared by Condensed / CondensedOverActive)
#
# A serving plan refreshes against a LIVE job, so the re-export must not
# transiently hold two copies of a stack's condensed weights. These run the
# re-condense / values-regather as ONE jitted program with the old buffers
# donated: when the new leaf has the same avals (fan-in k and active-row
# count unchanged — the common case for a DST step, which rewires at
# constant fan-in), XLA writes the new arrays into the donated buffers and
# the old jax.Arrays are invalidated at dispatch. keep_unused=True stops jit
# from pruning the donated args (the output aliases them by shape/dtype, not
# dataflow). No weight data ever crosses to the host.
# ---------------------------------------------------------------------------


def _condense_active_stack(weight, mask, k: int, a: int, tp: int = 1):
    """Condensed-over-active arrays for one stack (vmapped over lead dims).

    Drops ablated output neurons FIRST (Fig. 4's "structured" move), then
    condenses only the surviving columns to constant fan-in ``k``. ``a`` is
    the (static) max active-neuron count across the stack's replicas; rows
    beyond a replica's realized active count are padding with values 0 and
    an out-of-range ``out_index`` so the scatter in kernels.ops drops them.

    A neuron is treated as active iff its mask column has any non-zero —
    derived from the mask itself (not the trainer's neuron_active
    bookkeeping) so the representation is exact vs masked-dense by
    construction.

    ``tp > 1`` builds the shard-blocked TP layout instead: the output axis
    splits into ``tp`` contiguous blocks, each condensed independently to
    ``a`` surviving rows (``a`` is then the PER-SHARD bound), with
    ``out_index`` rebased block-locally (sentinel ``d_out // tp``). The
    returned arrays are the tp=1 shapes with ``tp * a`` total rows, grouped
    by block.
    """
    d_out = weight.shape[-1]

    def fn(w, m):
        col_active = jnp.any(m, axis=0)                      # (d_out,)
        order = jnp.argsort(~col_active, stable=True).astype(jnp.int32)
        out_index = order[:a]                                # active cols first
        sel = col_active[out_index]                          # (a,)
        w_sel = jnp.take(w, out_index, axis=1)
        m_sel = jnp.take(m, out_index, axis=1) & sel[None, :]
        vals, idx = topology.dense_to_condensed(w_sel * m_sel, m_sel, k)
        return vals, idx, jnp.where(sel, out_index, d_out).astype(jnp.int32)

    if tp <= 1:
        return _map_lead(fn, weight.ndim - 2)(weight, mask)

    wloc = d_out // tp

    def blk(w_s, m_s):
        col_active = jnp.any(m_s, axis=0)                    # (wloc,)
        order = jnp.argsort(~col_active, stable=True).astype(jnp.int32)
        out_index = order[:a]
        sel = col_active[out_index]
        w_sel = jnp.take(w_s, out_index, axis=1)
        m_sel = jnp.take(m_s, out_index, axis=1) & sel[None, :]
        vals, idx = topology.dense_to_condensed(w_sel * m_sel, m_sel, k)
        # indices address the FULL d_in rows (x stays replicated under TP);
        # out_index is block-LOCAL with the per-shard sentinel wloc
        return vals, idx, jnp.where(sel, out_index, wloc).astype(jnp.int32)

    def fn_tp(w, m):
        d_in = w.shape[0]
        wb = jnp.moveaxis(w.reshape(d_in, tp, wloc), 1, 0)   # (tp, d_in, wloc)
        mb = jnp.moveaxis(m.reshape(d_in, tp, wloc), 1, 0)
        vals, idx, oi = jax.vmap(blk)(wb, mb)                # (tp, a, ...)
        return (vals.reshape(tp * a, k), idx.reshape(tp * a, k),
                oi.reshape(tp * a))

    return _map_lead(fn_tp, weight.ndim - 2)(weight, mask)


@functools.partial(jax.jit, static_argnames=("k",), donate_argnums=(2, 3),
                   keep_unused=True)
def _recondense_donated(weight, mask, old_values, old_indices, *, k: int):
    fn = lambda w, m: topology.dense_to_condensed(w * m, m, k)
    vals, idx = _map_lead(fn, weight.ndim - 2)(weight, mask)
    return vals.astype(old_values.dtype), idx


@functools.partial(jax.jit, static_argnames=("k", "a", "tp"),
                   donate_argnums=(2, 3, 4), keep_unused=True)
def _recondense_active_donated(weight, mask, old_values, old_indices,
                               old_out_index, *, k: int, a: int, tp: int = 1):
    vals, idx, oi = _condense_active_stack(weight, mask, k, a, tp)
    return vals.astype(old_values.dtype), idx, oi


def _gather_at_indices(weight, mask, indices, out_index=None):
    def fn(w, m, idx, oi=None):
        wm_t = (w * m).T                                     # (d_out, d_in)
        if oi is not None:  # select surviving columns (clip: padding dropped)
            wm_t = jnp.take(wm_t, jnp.minimum(oi, wm_t.shape[0] - 1), axis=0)
        return jnp.take_along_axis(wm_t, idx, axis=1)

    n_lead = weight.ndim - 2
    if out_index is None:
        return _map_lead(fn, n_lead)(weight, mask, indices)
    return _map_lead(fn, n_lead)(weight, mask, indices, out_index)


@functools.partial(jax.jit, donate_argnums=(2,), keep_unused=True)
def _revalue_donated(weight, mask, old_values, indices):
    return _gather_at_indices(weight, mask, indices).astype(old_values.dtype)


@functools.partial(jax.jit, donate_argnums=(2,), keep_unused=True)
def _revalue_active_donated(weight, mask, old_values, indices, out_index):
    return _gather_at_indices(weight, mask, indices,
                              out_index).astype(old_values.dtype)


# quantized variants: same donation contract, with the per-neuron quantize
# epilogue fused into the jitted program so the new int8/fp8 values and f32
# scales are written straight into the OLD quantized buffers — a live
# quantized plan refreshes without ever holding a float copy of the stack.

@functools.partial(jax.jit, static_argnames=("k", "qdt"),
                   donate_argnums=(2, 3, 4), keep_unused=True)
def _recondense_quantized_donated(weight, mask, old_values, old_indices,
                                  old_scales, *, k: int, qdt: str):
    fn = lambda w, m: topology.dense_to_condensed(w * m, m, k)
    vals, idx = _map_lead(fn, weight.ndim - 2)(weight, mask)
    q, s = quantize_values(vals, qdt)
    return q, idx, s


@functools.partial(jax.jit, static_argnames=("k", "a", "qdt", "tp"),
                   donate_argnums=(2, 3, 4, 5), keep_unused=True)
def _recondense_active_quantized_donated(weight, mask, old_values, old_indices,
                                         old_out_index, old_scales, *,
                                         k: int, a: int, qdt: str,
                                         tp: int = 1):
    vals, idx, oi = _condense_active_stack(weight, mask, k, a, tp)
    q, s = quantize_values(vals, qdt)
    return q, idx, oi, s


@functools.partial(jax.jit, static_argnames=("qdt",), donate_argnums=(2, 3),
                   keep_unused=True)
def _revalue_quantized_donated(weight, mask, old_values, old_scales, indices,
                               *, qdt: str):
    return quantize_values(_gather_at_indices(weight, mask, indices), qdt)


@functools.partial(jax.jit, static_argnames=("qdt",), donate_argnums=(2, 3),
                   keep_unused=True)
def _revalue_active_quantized_donated(weight, mask, old_values, old_scales,
                                      indices, out_index, *, qdt: str):
    return quantize_values(
        _gather_at_indices(weight, mask, indices, out_index), qdt)


def _gather_active_panel(weight, mask, active_index):
    """(lead..., d_in, a_pad) surviving-column panel of ``weight * mask``.
    Sentinel (padding) slots are zeroed so they quantize to exact 0 and
    never pollute a real column's scale."""
    def fn(w, m, ai):
        d_out = w.shape[-1]
        g = jnp.take(w * m, jnp.minimum(ai, d_out - 1), axis=1)
        return jnp.where((ai < d_out)[None, :], g, 0.0)

    return _map_lead(fn, weight.ndim - 2)(weight, mask, active_index)


@functools.partial(jax.jit, static_argnames=("qdt",), donate_argnums=(3, 4),
                   keep_unused=True)
def _revalue_structured_quantized_donated(weight, mask, active_index,
                                          old_values, old_scales, *, qdt: str):
    return quantize_values(_gather_active_panel(weight, mask, active_index),
                           qdt, axis=-2)


def is_quantized_storage(arr_or_dtype) -> bool:
    """Is this array (or dtype) stored in one of the quantized values
    dtypes? Used by checkpoint restore to decide when a template/archive
    dtype mismatch means "re-/de-quantize" rather than "cast"."""
    dt = jnp.dtype(getattr(arr_or_dtype, "dtype", arr_or_dtype))
    return any(jnp.dtype(VALUES_DTYPES[n]) == dt for n in QUANTIZED_DTYPES
               if n in VALUES_DTYPES)


def _finalize_quantized_restore(fmt, *, axis: int = -1):
    """Reconcile a restored format's values/scales with its declared
    ``values_dtype`` (see ``SparseFormat.restore_finalize``). ``axis`` is
    the per-neuron reduction axis of the class's scale convention."""
    vals = fmt.values
    if vals is None or isinstance(vals, jax.ShapeDtypeStruct):
        return fmt
    declared = fmt.values_dtype
    if declared in QUANTIZED_DTYPES:
        if jnp.issubdtype(vals.dtype, jnp.floating) \
                and not is_quantized_storage(vals):
            q, s = quantize_values(vals, declared, axis=axis)
            return dataclasses.replace(fmt, values=q, scales=s)
        return fmt
    if is_quantized_storage(vals) and fmt.scales is not None:
        # quantized archive into a float template: dequantize and drop scales
        deq = dequantize_values(vals, fmt.scales, axis=axis)
        return dataclasses.replace(fmt, values=deq, scales=None)
    return fmt


# ---------------------------------------------------------------------------
# the four formats
# ---------------------------------------------------------------------------


@_register
@dataclasses.dataclass(frozen=True, eq=False)
class MaskedDense(SparseFormat):
    """Training layout: dense weight + bool mask, dense MXU matmul.

    ``weight_itemsize`` (static) records the dense weight's dtype bytes so
    the instance can price its own HBM traffic without seeing the weight.
    """
    mask: jax.Array                      # (lead..., d_in, d_out) bool
    weight_itemsize: int = 4

    format_name: typing.ClassVar[str] = "masked"
    _array_fields: typing.ClassVar[tuple[str, ...]] = ("mask",)
    _static_fields: typing.ClassVar[tuple[str, ...]] = ("weight_itemsize",)

    def apply(self, x, w=None):
        return x @ apply_mask_for_forward(w, self.mask).astype(x.dtype)

    @classmethod
    def export_from_dense(cls, w, mask, stats=None):
        return cls(mask=mask, weight_itemsize=jnp.dtype(w.dtype).itemsize)

    def spec(self) -> FormatSpec:
        d_in, d_out = self.mask.shape[-2:]
        n = 1
        for s in self.mask.shape[:-2]:
            n *= s
        return FormatSpec(d_in=d_in, d_out=d_out, n_replicas=n,
                          itemsize=self.weight_itemsize, k=d_in,
                          max_active=d_out, active_fraction=1.0)

    @classmethod
    def estimate_cost(cls, spec, batch, profile):
        b = max(int(batch), 1)
        flops = 2.0 * b * spec.n_replicas * spec.d_in * spec.d_out
        return max(cls.estimate_weight_bytes(spec) / profile.hbm_bytes_per_s,
                   flops / profile.mxu_flops_per_s)

    @classmethod
    def estimate_weight_bytes(cls, spec):
        # dense weight + the bool mask the masked path also reads
        return spec.n_replicas * spec.d_in * spec.d_out * (spec.itemsize + 1)

    @classmethod
    def estimate_cost_sharded(cls, spec, batch, profile, tp):
        # masked-dense is the REPLICATED path under TP: each device serves a
        # data-parallel replica of the dense weight (full HBM stream, zero
        # collectives) — the alternative the collective-priced sharded
        # formats are compared against
        return cls.estimate_cost(spec, batch, profile)

    @classmethod
    def abstract(cls, lead, d_in, d_out, k, dtype):
        return cls(mask=jax.ShapeDtypeStruct((*lead, d_in, d_out), jnp.bool_),
                   weight_itemsize=jnp.dtype(dtype).itemsize)

    def donate_refresh(self, w, mask, stats=None, *, donate=True):
        return type(self).export_from_dense(w, mask, stats)


@_register
@dataclasses.dataclass(frozen=True, eq=False)
class StructuredFanIn(SparseFormat):
    """Fig. 4 "structured": ablated neurons dropped, active columns dense.

    Executed by the column-gathered Pallas kernel
    (``kernels.ops.structured_linear`` over ``active_index`` — surviving
    column ids padded to the 128-lane tile with the ``d_out`` sentinel): the
    matmul runs over only the ``a_pad`` surviving columns and a fused
    scatter epilogue writes exact zeros for ablated neurons, so per-step HBM
    weight bytes and MXU FLOPs scale with the active fraction.
    ``estimate_cost`` prices exactly that (padded) execution. Exact only for
    ablation-only masks — bit-identical to ``ops.structured_dense``.
    ``active_index=None`` (legacy instances built before the field existed)
    falls back to the reference full-dense path.
    """
    neuron_active: jax.Array             # (lead..., d_out) bool
    active_index: jax.Array | None = None  # (lead..., a_pad) int32, pad=d_out
    d_in: int = 0                        # dense weight fan-in (for pricing)
    weight_itemsize: int = 4
    values: jax.Array | None = None      # (lead..., d_in, a_pad) quantized panel
    scales: jax.Array | None = None      # (lead..., a_pad) f32 per column
    values_dtype: str | None = None      # canonical name when quantized
    tp: int = 1                          # shard-blocked TP layout when > 1

    format_name: typing.ClassVar[str] = "structured"
    _array_fields: typing.ClassVar[tuple[str, ...]] = ("neuron_active",
                                                       "active_index",
                                                       "values", "scales")
    _static_fields: typing.ClassVar[tuple[str, ...]] = ("d_in",
                                                        "weight_itemsize",
                                                        "values_dtype", "tp")

    def apply(self, x, w=None):
        if self.tp > 1:
            # shard-blocked layout: active_index is grouped in tp blocks and
            # LOCALLY rebased (sentinel d_out // tp) — the vmap-over-blocks
            # ops partition shard-locally under a 'model'-sharded block axis
            if self.values is not None and self.active_index is not None:
                panel = dequantize_values(self.values, self.scales, axis=-2,
                                          dtype=x.dtype)
                return ops.structured_gathered_linear_tp_nd(
                    x, panel, self.active_index,
                    self.neuron_active.shape[-1], self.tp)
            return ops.structured_linear_tp_nd(x, w, self.active_index,
                                               self.tp)
        if self.values is not None and self.active_index is not None:
            # quantized export: the gathered active-column panel is stored
            # in the format itself; dequantize the 1-byte stream and feed
            # the pre-gathered kernel path (no live-weight read, no
            # column-gather pass)
            panel = dequantize_values(self.values, self.scales, axis=-2,
                                      dtype=x.dtype)
            return ops.structured_gathered_linear_nd(
                x, panel, self.active_index, self.neuron_active.shape[-1],
                values_dtype=self.values_dtype)
        if self.active_index is None:
            return ops.structured_dense(x, w.astype(x.dtype),
                                        self.neuron_active)
        return ops.structured_linear_nd(x, w, self.active_index)

    @classmethod
    def export_from_dense(cls, w, mask, stats=None, *, quantize_spec=None,
                          tp_shards: int = 1):
        stats = stats if stats is not None else _realized_stats(mask)
        d_out = int(mask.shape[-1])
        tp = _check_tp_shards(d_out, tp_shards)
        if tp > 1:
            # per-block surviving-column ids, LOCALLY rebased (sentinel
            # d_out // tp), grouped into one (lead..., tp * a_pad) vector
            wloc = d_out // tp
            act = jnp.any(mask, axis=-2)
            a_pad = padded_active_count(_per_shard_active_bound(mask, tp),
                                        wloc)
            blocks = act.reshape(*act.shape[:-1], tp, wloc)
            ai = active_index_from_bools(blocks, a_pad)
            ai = ai.reshape(*act.shape[:-1], tp * a_pad)
        else:
            a_pad = padded_active_count(max(stats.max_active, 1), d_out)
            ai = active_index_from_mask(mask, a_pad)
        qdt = resolve_quantize_spec(quantize_spec)
        vals = scales = None
        if qdt in QUANTIZED_DTYPES:
            gi = _rebased_global_index(ai, tp, d_out) if tp > 1 else ai
            vals, scales = quantize_values(_gather_active_panel(w, mask, gi),
                                           qdt, axis=-2)
        else:
            qdt = None  # a bare storage cast has nothing to store here
        return cls(neuron_active=jnp.any(mask, axis=-2), active_index=ai,
                   d_in=int(mask.shape[-2]),
                   weight_itemsize=jnp.dtype(w.dtype).itemsize,
                   values=vals, scales=scales, values_dtype=qdt, tp=tp)

    def _a_pad(self) -> int:
        d_out = self.neuron_active.shape[-1]
        return (self.active_index.shape[-1] if self.active_index is not None
                else padded_active_count(d_out, d_out))

    def spec(self) -> FormatSpec:
        d_out = self.neuron_active.shape[-1]
        n = 1
        for s in self.neuron_active.shape[:-1]:
            n *= s
        a_pad = self._a_pad()
        return FormatSpec(d_in=self.d_in, d_out=d_out, n_replicas=n,
                          itemsize=self.weight_itemsize, k=self.d_in,
                          max_active=a_pad,
                          active_fraction=min(a_pad / max(d_out, 1), 1.0),
                          values_dtype=self.values_dtype, tp=self.tp)

    @classmethod
    def estimate_cost(cls, spec, batch, profile):
        # priced at the EXPORTED (lane-padded) column count the kernel runs
        # over; the compute term includes the fused one-hot scatter epilogue
        # (an MXU matmul of the compact tile against the selection matrix)
        b = max(int(batch), 1)
        a_pad = padded_active_count(spec.max_active, spec.d_out)
        flops = 2.0 * b * spec.n_replicas * a_pad * (spec.d_in + spec.d_out)
        return max(cls.estimate_weight_bytes(spec) / profile.hbm_bytes_per_s,
                   flops / profile.mxu_flops_per_s)

    @classmethod
    def estimate_weight_bytes(cls, spec):
        # the gathered (d_in, a_pad) weight panel (real stored width, + the
        # f32 per-column scale when quantized) + the int32 active_index;
        # neuron_active is not read on the gathered hot path
        a_pad = padded_active_count(spec.max_active, spec.d_out)
        return cls.estimate_values_bytes(spec) + spec.n_replicas * a_pad * 4

    @classmethod
    def estimate_values_bytes(cls, spec):
        a_pad = padded_active_count(spec.max_active, spec.d_out)
        vb = spec.n_replicas * spec.d_in * a_pad * values_itemsize(spec)
        if spec.values_dtype in QUANTIZED_DTYPES:
            vb += spec.n_replicas * a_pad * 4
        return vb

    def tuning_key(self, batch, *, backend=None):
        if self.active_index is None:
            return None  # legacy instance: reference path, nothing to tune
        # per-SHARD shapes under TP: a tuned entry describes the block one
        # device executes (the backend-keyed cache machinery is unchanged)
        return shape_tuning_key(
            self.d_in, self._a_pad() // self.tp, 0, batch, backend=backend,
            itemsize=self.weight_itemsize, kind="structured",
            scatter_width=self.neuron_active.shape[-1] // self.tp,
            values_dtype=self.values_dtype)

    @classmethod
    def spec_tuning_key(cls, spec, batch, *, backend=None):
        s = cls.shard_spec(spec, spec.tp)
        a_pad = padded_active_count(s.max_active, s.d_out)
        return shape_tuning_key(s.d_in, a_pad, 0, batch, backend=backend,
                                itemsize=s.itemsize, kind="structured",
                                scatter_width=s.d_out,
                                values_dtype=s.values_dtype)

    @classmethod
    def abstract(cls, lead, d_in, d_out, k, dtype, tp: int = 1):
        # a_pad = padded d_out static bound (no realized ablation counts at
        # lowering time); the concrete export shrinks it to the real count.
        # Under TP each of the tp blocks pads independently.
        tp = _check_tp_shards(d_out, tp)
        wloc = d_out // tp
        a_pad = padded_active_count(wloc, wloc) * tp
        return cls(neuron_active=jax.ShapeDtypeStruct((*lead, d_out),
                                                      jnp.bool_),
                   active_index=jax.ShapeDtypeStruct((*lead, a_pad),
                                                     jnp.int32),
                   d_in=d_in, weight_itemsize=jnp.dtype(dtype).itemsize,
                   tp=tp)

    def donate_refresh(self, w, mask, stats=None, *, donate=True):
        return type(self).export_from_dense(w, mask, stats,
                                            quantize_spec=self.values_dtype,
                                            tp_shards=self.tp)

    def refresh_values(self, w, mask, *, donate: bool = True):
        """No-op for float instances (they read the live weights). Quantized
        instances hold a stale panel: regather + requantize at the stored
        active_index, donated into the old 1-byte buffers."""
        if self.values is None or self.active_index is None:
            return self
        # TP instances store LOCAL column ids — rebase to the global output
        # axis for the dense-weight regather (layout reproduced exactly)
        ai = (_rebased_global_index(self.active_index, self.tp,
                                    self.neuron_active.shape[-1])
              if self.tp > 1 else self.active_index)
        if donate:
            vals, s = _revalue_structured_quantized_donated(
                w, mask, ai, self.values, self.scales,
                qdt=self.values_dtype)
        else:
            vals, s = quantize_values(
                _gather_active_panel(w, mask, ai),
                self.values_dtype, axis=-2)
        return dataclasses.replace(self, values=vals, scales=s)

    def rebuild_missing(self, missing):
        # archives written before active_index existed: derive it from the
        # RESTORED neuron_active, sized by the restored masks' realized
        # active count — NOT the template's length, which was sized from the
        # template's own (e.g. fresh-init) masks and may be too short for
        # the archive's actives (a too-short vector would silently zero the
        # overflow columns). Restore runs host-side on concrete arrays, so
        # the one scalar sync is fine here.
        out = self
        if "active_index" in missing and "neuron_active" not in missing \
                and self.active_index is not None:
            act = self.neuron_active
            if self.tp > 1:
                # TP templates rebuild the shard-blocked LOCAL layout
                wloc = act.shape[-1] // self.tp
                blocks = act.reshape(*act.shape[:-1], self.tp, wloc)
                realized = int(jax.device_get(jnp.max(
                    jnp.sum(blocks.astype(jnp.int32), axis=-1))))
                a_pad = padded_active_count(max(realized, 1), wloc)
                ai = active_index_from_bools(blocks, a_pad)
                ai = ai.reshape(*act.shape[:-1], self.tp * a_pad)
            else:
                realized = int(jax.device_get(
                    jnp.max(jnp.sum(act.astype(jnp.int32), axis=-1))))
                a_pad = padded_active_count(max(realized, 1), act.shape[-1])
                ai = active_index_from_bools(act, a_pad)
            out = dataclasses.replace(out, active_index=ai)
        if "values" in missing and out.values_dtype in QUANTIZED_DTYPES:
            # the archive predates the quantized panel and the panel cannot
            # be rebuilt without the live dense weight: degrade to the
            # live-weight (unquantized) execution path; the next
            # donate_refresh re-exports the panel at the declared dtype
            return dataclasses.replace(out, values=None, scales=None)
        if "scales" in missing and out.values_dtype in QUANTIZED_DTYPES:
            out = out.restore_finalize()
        return out

    def restore_finalize(self):
        return _finalize_quantized_restore(self, axis=-2)


@_register
@dataclasses.dataclass(frozen=True, eq=False)
class Condensed(SparseFormat):
    """Fig. 4 "condensed": the constant fan-in gather layout (paper Alg. 1).

    ``d_in`` (static) is the dense fan-in the indices address — needed for
    the autotune cache key (the kernel's VMEM footprint depends on the
    activation row length), not for ``apply``.

    Quantized exports (``quantize_spec="int8"``/``"fp8"``) store ``values``
    at 1 byte/element with a per-neuron float32 ``scales`` row-scale; the
    dequantize (one multiply per OUTPUT, after the k-reduction) is fused
    into the Pallas gather kernel, so the decode hot path streams the weight
    values at the quantized width. ``values_dtype`` (static) records the
    declared storage dtype so checkpoint restore can re-quantize a float
    archive into this template.
    """
    values: jax.Array                    # (lead..., d_out, k)
    indices: jax.Array                   # (lead..., d_out, k) int32
    d_in: int = 0
    scales: jax.Array | None = None      # (lead..., d_out) f32 when quantized
    values_dtype: str | None = None      # canonical name when quantized
    tp: int = 1                          # shard-blocked TP execution when > 1

    format_name: typing.ClassVar[str] = "condensed"
    _array_fields: typing.ClassVar[tuple[str, ...]] = ("values", "indices",
                                                       "scales")
    _static_fields: typing.ClassVar[tuple[str, ...]] = ("d_in", "values_dtype",
                                                        "tp")

    def apply(self, x, w=None):
        if self.tp > 1:
            # the plain condensed layout's contiguous neuron rows ARE the
            # shard blocks (constant fan-in: exactly balanced) — no array
            # reorganization, only the vmap-over-blocks execution
            return ops.condensed_linear_tp_nd(x, self.values, self.indices,
                                              self.tp, scales=self.scales)
        # values pass as stored: the kernel rounds them to x's dtype in VMEM
        return ops.condensed_linear_nd(x, self.values, self.indices,
                                       scales=self.scales)

    @classmethod
    def export_from_dense(cls, w, mask, stats=None, *, quantize_spec=None,
                          tp_shards: int = 1):
        stats = stats if stats is not None else _realized_stats(mask)
        k = max(stats.k, 1)
        # the exported arrays are IDENTICAL for every tp: contiguous neuron
        # rows already partition into equal blocks (validated divisible)
        tp = _check_tp_shards(int(w.shape[-1]), tp_shards)
        fn = lambda w_, m_: topology.dense_to_condensed(w_ * m_, m_, k)
        vals, idx = _map_lead(fn, w.ndim - 2)(w, mask)
        qdt = resolve_quantize_spec(quantize_spec)
        if qdt in QUANTIZED_DTYPES:
            q, s = quantize_values(vals, qdt)
            return cls(values=q, indices=idx, d_in=int(w.shape[-2]),
                       scales=s, values_dtype=qdt, tp=tp)
        if qdt is not None:  # plain storage-dtype cast (e.g. bf16)
            vals = vals.astype(VALUES_DTYPES[qdt])
        return cls(values=vals, indices=idx, d_in=int(w.shape[-2]), tp=tp)

    def spec(self) -> FormatSpec:
        d_out, k = self.values.shape[-2:]
        n = 1
        for s in self.values.shape[:-2]:
            n *= s
        quantized = self.values_dtype in QUANTIZED_DTYPES
        itemsize = (jnp.dtype(self.scales.dtype).itemsize
                    if quantized and self.scales is not None
                    else jnp.dtype(self.values.dtype).itemsize)
        return FormatSpec(d_in=self.d_in, d_out=d_out, n_replicas=n,
                          itemsize=itemsize, k=k, max_active=d_out,
                          active_fraction=1.0,
                          values_dtype=self.values_dtype, tp=self.tp)

    @classmethod
    def estimate_cost(cls, spec, batch, profile):
        b = max(int(batch), 1)
        gather_flops = 2.0 * b * spec.n_replicas * spec.d_out * spec.k
        return max(cls.estimate_weight_bytes(spec) / profile.hbm_bytes_per_s,
                   gather_flops / _gather_rate(profile, b))

    @classmethod
    def estimate_weight_bytes(cls, spec):
        # values at the real stored width + int32 indices (+ the f32 scales
        # row when quantized)
        return (cls.estimate_values_bytes(spec)
                + spec.n_replicas * spec.d_out * spec.k * 4)

    @classmethod
    def estimate_values_bytes(cls, spec):
        vb = spec.n_replicas * spec.d_out * spec.k * values_itemsize(spec)
        if spec.values_dtype in QUANTIZED_DTYPES:
            vb += spec.n_replicas * spec.d_out * 4  # per-neuron f32 scale
        return vb

    def tuning_key(self, batch, *, backend=None):
        d_out, k = self.values.shape[-2:]
        # per-SHARD shapes under TP (d_out shrinks by 1/tp; same cache)
        return shape_tuning_key(
            self.d_in, d_out // self.tp, k, batch, backend=backend,
            itemsize=jnp.dtype(self.values.dtype).itemsize,
            values_dtype=self.values_dtype)

    @classmethod
    def spec_tuning_key(cls, spec, batch, *, backend=None):
        s = cls.shard_spec(spec, spec.tp)
        return shape_tuning_key(s.d_in, s.d_out, s.k, batch,
                                backend=backend, itemsize=s.itemsize,
                                values_dtype=s.values_dtype)

    @classmethod
    def abstract(cls, lead, d_in, d_out, k, dtype, tp: int = 1):
        shape = (*lead, d_out, k)
        return cls(values=jax.ShapeDtypeStruct(shape, jnp.dtype(dtype)),
                   indices=jax.ShapeDtypeStruct(shape, jnp.int32), d_in=d_in,
                   tp=_check_tp_shards(d_out, tp))

    def donate_refresh(self, w, mask, stats=None, *, donate=True):
        stats = stats if stats is not None else _realized_stats(mask)
        k = max(stats.k, 1)
        shape = (*w.shape[:-2], w.shape[-1], k)
        if donate and self.values.shape == shape:
            # the donated re-condense is tp-agnostic: the arrays' layout is
            # identical for every tp (contiguous row blocks), so the static
            # tp rides through dataclasses.replace unchanged
            if (self.values_dtype in QUANTIZED_DTYPES
                    and self.scales is not None):
                vals, idx, s = _recondense_quantized_donated(
                    w, mask, self.values, self.indices, self.scales,
                    k=k, qdt=self.values_dtype)
                return dataclasses.replace(self, values=vals, indices=idx,
                                           scales=s)
            if self.values.dtype == w.dtype:
                vals, idx = _recondense_donated(w, mask, self.values,
                                                self.indices, k=k)
                return dataclasses.replace(self, values=vals, indices=idx)
        return type(self).export_from_dense(w, mask, stats,
                                            quantize_spec=self.values_dtype,
                                            tp_shards=self.tp)

    def refresh_values(self, w, mask, *, donate: bool = True):
        """Regather ``w * mask`` at the stored indices (topology unchanged).

        Exact because padding slots point at inactive rows
        (dense_to_condensed's invariant), so they re-gather exact zeros.
        ``donate=True`` writes the new values into the OLD values buffer
        (see the donated-program block comment); indices are reused
        verbatim either way. Quantized instances re-quantize in the same
        donated program (fresh scales from the regathered rows).
        """
        if self.values_dtype in QUANTIZED_DTYPES and self.scales is not None:
            if donate:
                vals, s = _revalue_quantized_donated(
                    w, mask, self.values, self.scales, self.indices,
                    qdt=self.values_dtype)
            else:
                vals, s = quantize_values(_gather_at_indices(w, mask,
                                                             self.indices),
                                          self.values_dtype)
            return dataclasses.replace(self, values=vals, scales=s)
        if donate:
            vals = _revalue_donated(w, mask, self.values, self.indices)
        else:
            vals = _gather_at_indices(w, mask,
                                      self.indices).astype(self.values.dtype)
        return dataclasses.replace(self, values=vals)

    def rebuild_missing(self, missing):
        # a pre-quantization archive restored into a quantized template has
        # no scales: re-derive them (and the quantized codes) from the
        # restored float values
        if "scales" in missing and self.values_dtype in QUANTIZED_DTYPES:
            return self.restore_finalize()
        return self

    def restore_finalize(self):
        return _finalize_quantized_restore(self)


@_register
@dataclasses.dataclass(frozen=True, eq=False)
class CondensedOverActive(SparseFormat):
    """Fig. 4's combined point: drop ablated neurons, condense survivors.

    values/indices cover only the ``a <= d_out`` surviving rows;
    ``out_index`` scatters each surviving row back into the dense output
    layout (out-of-range entries mark padding rows, dropped at scatter).
    Exact for ANY mask — ablated outputs are exact zeros either way.
    """
    values: jax.Array                    # (lead..., a, k)
    indices: jax.Array                   # (lead..., a, k) int32
    out_index: jax.Array                 # (lead..., a) int32
    d_in: int = 0
    d_out: int = 0                       # dense output width (scatter target)
    scales: jax.Array | None = None      # (lead..., a) f32 when quantized
    values_dtype: str | None = None      # canonical name when quantized
    tp: int = 1                          # shard-blocked TP layout when > 1

    format_name: typing.ClassVar[str] = "condensed_over_active"
    _array_fields: typing.ClassVar[tuple[str, ...]] = ("values", "indices",
                                                       "out_index", "scales")
    _static_fields: typing.ClassVar[tuple[str, ...]] = ("d_in", "d_out",
                                                        "values_dtype", "tp")

    def apply(self, x, w=None):
        if self.tp > 1:
            # shard-blocked layout: rows grouped in tp blocks of a_tp, with
            # out_index LOCALLY rebased (sentinel d_out // tp) — the local
            # scatter never crosses shards
            return ops.condensed_over_active_linear_tp_nd(
                x, self.values, self.indices, self.out_index, self.d_out,
                self.tp, scales=self.scales)
        return ops.condensed_over_active_linear_nd(
            x, self.values, self.indices, self.out_index, self.d_out,
            scales=self.scales)

    @classmethod
    def export_from_dense(cls, w, mask, stats=None, *, quantize_spec=None,
                          tp_shards: int = 1):
        stats = stats if stats is not None else _realized_stats(mask)
        tp = _check_tp_shards(int(w.shape[-1]), tp_shards)
        # per-shard surviving-row bound: the max over BLOCKS, not replicas
        # (one host sync; export is host-driven like _realized_stats)
        a = (_per_shard_active_bound(mask, tp) if tp > 1
             else max(stats.max_active, 1))
        vals, idx, oi = _condense_active_stack(w, mask, max(stats.k, 1),
                                               a, tp)
        qdt = resolve_quantize_spec(quantize_spec)
        if qdt in QUANTIZED_DTYPES:
            q, s = quantize_values(vals, qdt)
            return cls(values=q, indices=idx, out_index=oi,
                       d_in=int(w.shape[-2]), d_out=int(w.shape[-1]),
                       scales=s, values_dtype=qdt, tp=tp)
        if qdt is not None:
            vals = vals.astype(VALUES_DTYPES[qdt])
        return cls(values=vals, indices=idx, out_index=oi,
                   d_in=int(w.shape[-2]), d_out=int(w.shape[-1]), tp=tp)

    def spec(self) -> FormatSpec:
        a, k = self.values.shape[-2:]
        n = 1
        for s in self.values.shape[:-2]:
            n *= s
        quantized = self.values_dtype in QUANTIZED_DTYPES
        itemsize = (jnp.dtype(self.scales.dtype).itemsize
                    if quantized and self.scales is not None
                    else jnp.dtype(self.values.dtype).itemsize)
        return FormatSpec(d_in=self.d_in, d_out=self.d_out, n_replicas=n,
                          itemsize=itemsize, k=k, max_active=a,
                          active_fraction=a / max(self.d_out, 1),
                          values_dtype=self.values_dtype, tp=self.tp)

    @classmethod
    def estimate_cost(cls, spec, batch, profile):
        # priced at the EXPORTED row fraction (max_active rows per replica,
        # padding included) — the kernel runs over all of them; the mean
        # active fraction would under-price the path under uneven ablation
        b = max(int(batch), 1)
        row_frac = min(max(spec.max_active / max(spec.d_out, 1), 0.0), 1.0)
        gather_flops = 2.0 * b * spec.n_replicas * spec.d_out * spec.k
        return max(cls.estimate_weight_bytes(spec) / profile.hbm_bytes_per_s,
                   row_frac * gather_flops / _gather_rate(profile, b))

    @classmethod
    def estimate_weight_bytes(cls, spec):
        # max_active rows of k values (real stored width) + k int32 indices
        # plus the 4-byte out_index (and f32 scale when quantized) per row
        return (cls.estimate_values_bytes(spec)
                + spec.n_replicas * spec.max_active * (spec.k * 4 + 4))

    @classmethod
    def estimate_values_bytes(cls, spec):
        vb = spec.n_replicas * spec.max_active * spec.k * values_itemsize(spec)
        if spec.values_dtype in QUANTIZED_DTYPES:
            vb += spec.n_replicas * spec.max_active * 4
        return vb

    def tuning_key(self, batch, *, backend=None):
        a, k = self.values.shape[-2:]
        # per-SHARD shapes under TP: a_tp rows scattered into d_out/tp
        return shape_tuning_key(
            self.d_in, a // self.tp, k, batch, backend=backend,
            itemsize=jnp.dtype(self.values.dtype).itemsize, kind="coa",
            scatter_width=self.d_out // self.tp,
            values_dtype=self.values_dtype)

    @classmethod
    def spec_tuning_key(cls, spec, batch, *, backend=None):
        # the FUSED kernel runs over the (max_active, k) arrays the export
        # built and scatters into the d_out-wide output block in-kernel —
        # both are part of its key (kind="coa")
        s = cls.shard_spec(spec, spec.tp)
        return shape_tuning_key(s.d_in, s.max_active, s.k, batch,
                                backend=backend, itemsize=s.itemsize,
                                kind="coa", scatter_width=s.d_out,
                                values_dtype=s.values_dtype)

    @classmethod
    def abstract(cls, lead, d_in, d_out, k, dtype, tp: int = 1):
        # a = d_out static bound (no realized ablation counts at lowering
        # time); the concrete export shrinks a to the real max active count.
        # Under TP the bound is d_out/tp per block — tp blocks of it give
        # the SAME global shapes, only the static tp differs.
        shape = (*lead, d_out, k)
        return cls(values=jax.ShapeDtypeStruct(shape, jnp.dtype(dtype)),
                   indices=jax.ShapeDtypeStruct(shape, jnp.int32),
                   out_index=jax.ShapeDtypeStruct((*lead, d_out), jnp.int32),
                   d_in=d_in, d_out=d_out, tp=_check_tp_shards(d_out, tp))

    def donate_refresh(self, w, mask, stats=None, *, donate=True):
        stats = stats if stats is not None else _realized_stats(mask)
        k = max(stats.k, 1)
        a = (_per_shard_active_bound(mask, self.tp) if self.tp > 1
             else max(stats.max_active, 1))
        shape = (*w.shape[:-2], self.tp * a, k)
        if donate and self.values.shape == shape:
            if (self.values_dtype in QUANTIZED_DTYPES
                    and self.scales is not None):
                vals, idx, oi, s = _recondense_active_quantized_donated(
                    w, mask, self.values, self.indices, self.out_index,
                    self.scales, k=k, a=a, qdt=self.values_dtype, tp=self.tp)
                return dataclasses.replace(self, values=vals, indices=idx,
                                           out_index=oi, scales=s)
            if self.values.dtype == w.dtype:
                vals, idx, oi = _recondense_active_donated(
                    w, mask, self.values, self.indices, self.out_index,
                    k=k, a=a, tp=self.tp)
                return dataclasses.replace(self, values=vals, indices=idx,
                                           out_index=oi)
        return type(self).export_from_dense(w, mask, stats,
                                            quantize_spec=self.values_dtype,
                                            tp_shards=self.tp)

    def refresh_values(self, w, mask, *, donate: bool = True):
        """Values-only regather. Padding ROWS may re-gather garbage from a
        clipped column but are dropped by the out-of-range out_index at
        scatter time, so the representation stays exact. Quantized instances
        re-quantize (fresh scales) in the same donated program. TP instances
        rebase their local out_index to the global output axis for the
        dense-weight regather (same programs, same donation contract)."""
        oi = (_rebased_global_index(self.out_index, self.tp, self.d_out)
              if self.tp > 1 else self.out_index)
        if self.values_dtype in QUANTIZED_DTYPES and self.scales is not None:
            if donate:
                vals, s = _revalue_active_quantized_donated(
                    w, mask, self.values, self.scales, self.indices,
                    oi, qdt=self.values_dtype)
            else:
                vals, s = quantize_values(
                    _gather_at_indices(w, mask, self.indices, oi),
                    self.values_dtype)
            return dataclasses.replace(self, values=vals, scales=s)
        if donate:
            vals = _revalue_active_donated(w, mask, self.values, self.indices,
                                           oi)
        else:
            vals = _gather_at_indices(w, mask, self.indices,
                                      oi).astype(self.values.dtype)
        return dataclasses.replace(self, values=vals)

    def rebuild_missing(self, missing):
        if "scales" in missing and self.values_dtype in QUANTIZED_DTYPES:
            return self.restore_finalize()
        return self

    def restore_finalize(self):
        return _finalize_quantized_restore(self)


FORMATS: dict[str, type[SparseFormat]] = {
    cls.format_name: cls
    for cls in (MaskedDense, Condensed, StructuredFanIn, CondensedOverActive)
}

# formats whose exported arrays go stale as weights train (the rest read the
# live weights at execution time)
CONDENSED_FAMILY = (Condensed, CondensedOverActive)


# ---------------------------------------------------------------------------
# legacy dict-leaf deprecation shim
# ---------------------------------------------------------------------------

_LEGACY_KEYSETS: dict[frozenset, type[SparseFormat]] = {
    frozenset({"values", "indices"}): Condensed,
    frozenset({"values", "indices", "out_index"}): CondensedOverActive,
    frozenset({"neuron_active"}): StructuredFanIn,
}
_RESERVED_KEYS = frozenset({"values", "indices", "out_index", "neuron_active"})


def from_legacy_leaf(leaf: dict, *, d_in: int | None = None,
                     d_out: int | None = None,
                     warn: bool = True) -> SparseFormat:
    """Upgrade a pre-redesign serving dict leaf to its format object.

    Recognized key sets: ``{values, indices}`` -> Condensed,
    ``{values, indices, out_index}`` -> CondensedOverActive,
    ``{neuron_active}`` -> StructuredFanIn. A dict carrying any reserved key
    alongside unrecognized extras RAISES instead of silently mis-dispatching
    (the pre-redesign key-sniffing would have fallen through). ``d_in`` /
    ``d_out`` fill the static geometry the dict never carried (autotune keys
    need d_in; the scatter needs d_out — inferred from out_index's range
    bound is not possible without a host sync, so 0 means "unknown, tuned
    lookups disabled" unless the caller supplies it).
    """
    keys = frozenset(leaf)
    cls = _LEGACY_KEYSETS.get(keys)
    if cls is None:
        raise ValueError(
            f"unrecognized serving-leaf dict keys {sorted(keys)}: expected one "
            f"of {sorted(sorted(s) for s in _LEGACY_KEYSETS)} (legacy leaves) "
            f"or a repro.sparse.formats.SparseFormat instance")
    if warn:
        warnings.warn(
            "dict-style serving leaves are deprecated; build "
            f"repro.sparse.formats.{cls.__name__} objects instead",
            DeprecationWarning, stacklevel=2)
    if cls is Condensed:
        return Condensed(values=leaf["values"], indices=leaf["indices"],
                         d_in=int(d_in or 0))
    if cls is CondensedOverActive:
        if not d_out:
            # the scatter target width is NOT recoverable from the leaf's
            # arrays without a host sync — the pre-redesign dispatch read it
            # off the dense weight at call time
            raise ValueError(
                "upgrading a legacy condensed_over_active leaf requires "
                "d_out (the dense output width the out_index scatters into)")
        return CondensedOverActive(
            values=leaf["values"], indices=leaf["indices"],
            out_index=leaf["out_index"], d_in=int(d_in or 0),
            d_out=int(d_out))
    act = leaf["neuron_active"]
    d_out_real = act.shape[-1]
    # legacy dicts carry no realized active count (recovering one would need
    # a host sync) — build active_index at the padded d_out bound; a
    # re-export from the masks tightens it to the realized count
    return StructuredFanIn(
        neuron_active=act,
        active_index=active_index_from_bools(
            act, padded_active_count(d_out_real, d_out_real)),
        d_in=int(d_in or 0))


def is_legacy_leaf(node) -> bool:
    """Is this dict a pre-redesign serving leaf (or a malformed attempt)?"""
    return isinstance(node, dict) and bool(_RESERVED_KEYS & set(node))


def upgrade_serving_tree(tree, registry=None, *, warn: bool = True):
    """Walk a serving pytree and upgrade every legacy dict leaf in place
    (new tree returned; arrays shared). ``registry`` (iterable of
    SparseStack) fills d_in/d_out for leaves at known stack paths. Dicts
    with unrecognized reserved-key combinations raise."""
    geo = {}
    for s in (registry or []):
        geo[s.path] = (s.d_in, s.d_out)

    def rec(node, path):
        if is_legacy_leaf(node):
            d_in, d_out = geo.get(path, (None, None))
            return from_legacy_leaf(node, d_in=d_in, d_out=d_out, warn=warn)
        if isinstance(node, dict):
            return {k: rec(v, path + (k,)) for k, v in node.items()}
        return node

    return rec(tree, ())
