"""Condensed-representation export: masks -> serving-format pytrees.

The paper's serving story (Sec. 4.4): the SAME trained weights can execute
as masked-dense (MXU path, training/prefill) or condensed constant fan-in
(bandwidth path, decode/online inference). This module converts a trained
(params, masks) pair into serving pytrees whose leaves are the typed format
objects from ``repro.sparse.formats`` (the representation layer proper —
``apply``/``cost``/``tuning_key``/``donate_refresh`` all live there); what
stays here is the REGISTRY-LEVEL orchestration: fused per-stack stats with
one host sync, whole-tree exports, and byte accounting.

The per-leaf helpers (``condense_stack_leaf`` & co.) are kept as thin
delegates to the format constructors so pre-redesign callers keep working;
they now return ``SparseFormat`` objects (which still answer
``leaf["values"]``-style access during the migration).
"""
from __future__ import annotations

import typing

import jax
import jax.numpy as jnp

from repro.core import distributions as D
from repro.sparse import formats as F
from repro.sparse import registry as REG

# re-exports: these names predate the formats module and are widely imported
ExportStats = F.ExportStats


def export_stats(registry, masks: dict,
                 stacks: typing.Sequence | None = None) -> dict[str, ExportStats]:
    """Per-stack realized stats with ONE device program and ONE host sync.

    The naive per-stack ``int(jnp.max(...))`` forces a device->host transfer
    per stack (a serialization point on every export); here every stack's
    reductions are fused into a single stacked (n_stacks, 3) array and fetched
    with a single ``jax.device_get``. ``stacks`` optionally restricts the
    computation to a subset (incremental refresh re-measures only the stacks
    whose masks changed).
    """
    stacks = list(registry if stacks is None else stacks)
    rows = []
    for s in stacks:
        m = REG.get_path(masks, s.path)
        nnz = jnp.sum(m.astype(jnp.int32), axis=-2)          # (lead..., d_out)
        act = jnp.any(m, axis=-2)                            # (lead..., d_out)
        rows.append(jnp.stack([
            jnp.max(nnz).astype(jnp.float32),
            jnp.max(jnp.sum(act.astype(jnp.int32), axis=-1)).astype(jnp.float32),
            jnp.mean(act.astype(jnp.float32)),
            # min fan-in over ACTIVE columns: == d_in iff the mask is
            # ablation-ONLY (surviving columns fully dense) — the regime
            # where the structured representation is exact (plan auto)
            jnp.min(jnp.where(nnz > 0, nnz, m.shape[-2])).astype(jnp.float32),
        ]))
    if not rows:
        return {}
    table = jax.device_get(jnp.stack(rows))                  # single transfer
    return {s.name: ExportStats(k=int(r[0]), max_active=int(r[1]),
                                active_fraction=float(r[2]),
                                min_fan_in=int(r[3]))
            for s, r in zip(stacks, table)}


def stats_from_leaf(leaf, *, min_fan_in: int = 0) -> ExportStats:
    """ExportStats derived from an exported leaf's GEOMETRY (no mask).

    The sync subscriber adopts leaves that were exported remotely — the
    replica never holds the trainer's mask, so realized stats must come
    from the leaf's own shapes via ``leaf.spec()``. k / max_active are
    exact (they size the arrays); ``active_fraction`` is the spec's padded
    estimate and ``min_fan_in`` defaults to 0 ("unknown"), so a plan
    repriced from these stats can never enable the structured-exact path
    by accident.
    """
    spec = leaf.spec()
    return ExportStats(k=int(spec.k), max_active=int(spec.max_active),
                       active_fraction=float(spec.active_fraction),
                       min_fan_in=int(min_fan_in))


def _condense_stack(weight, mask, k: int):
    """Condensed arrays at forced fan-in ``k`` (exactness-test reference)."""
    from repro.core import topology
    fn = lambda w, m: topology.dense_to_condensed(w, m, k)
    vals, idx = F._map_lead(fn, weight.ndim - 2)(weight, mask)
    return {"values": vals, "indices": idx}


def condense_stack_leaf(weight, mask, stats: ExportStats) -> F.Condensed:
    """Condensed format for one stack at realized fan-in."""
    return F.Condensed.export_from_dense(weight, mask, stats)


def condense_active_stack_leaf(weight, mask,
                               stats: ExportStats) -> F.CondensedOverActive:
    return F.CondensedOverActive.export_from_dense(weight, mask, stats)


def structured_stack_leaf(mask, *, d_in: int | None = None,
                          weight_itemsize: int = 4,
                          stats: ExportStats | None = None) -> F.StructuredFanIn:
    """Structured-only format for one stack. A neuron is active iff its mask
    column has any non-zero (matches the trainer's neuron_active state after
    an SRigL update, and degrades gracefully for unstructured masks).
    ``stats`` (when precomputed) sizes the gathered kernel's ``active_index``
    at the realized active count without a host sync."""
    stats = stats if stats is not None else F._realized_stats(mask)
    d_out = int(mask.shape[-1])
    a_pad = F.padded_active_count(max(stats.max_active, 1), d_out)
    return F.StructuredFanIn(neuron_active=jnp.any(mask, axis=-2),
                             active_index=F.active_index_from_mask(mask, a_pad),
                             d_in=int(d_in if d_in is not None
                                      else mask.shape[-2]),
                             weight_itemsize=weight_itemsize)


def recondense_stack_leaf(weight, mask, stats: ExportStats, old_leaf, *,
                          over_active: bool = False,
                          donate: bool = True,
                          quantize_spec=None, tp: int = 1) -> F.SparseFormat:
    """Re-condense one stack for Plan.refresh, reusing ``old_leaf``'s device
    buffers when the new arrays' avals match (see the donated-program notes
    in repro.sparse.formats).

    CAUTION (donate=True): the arrays in ``old_leaf`` are invalidated —
    callers must not read them afterwards. Falls back to a fresh (non-
    donating) export when the realized fan-in / active count changed shape.
    Accepts legacy dict leaves through the deprecation shim.

    ``quantize_spec`` only matters on the fresh-export fallback (the plan's
    values dtype for a leaf whose representation just changed); the donated
    path re-exports under the OLD leaf's own ``values_dtype``, which for a
    plan-managed leaf is the same thing.

    ``tp`` is the plan's per-stack shard count: an old leaf exported at a
    DIFFERENT shard layout cannot be donated into (its block structure
    changed even when shapes match), so the refresh falls back to a fresh
    export at ``tp_shards=tp``.
    """
    if isinstance(old_leaf, dict):
        old_leaf = F.from_legacy_leaf(old_leaf, d_in=weight.shape[-2],
                                      d_out=weight.shape[-1])
    cls = F.CondensedOverActive if over_active else F.Condensed
    tp = max(int(tp), 1)
    if not isinstance(old_leaf, cls) or getattr(old_leaf, "tp", 1) != tp:
        # representation or shard layout changed: fresh export
        return cls.export_from_dense(weight, mask, stats,
                                     quantize_spec=quantize_spec,
                                     tp_shards=tp)
    return old_leaf.donate_refresh(weight, mask, stats, donate=donate)


def revalue_stack_leaf(weight, mask, leaf, *, donate: bool = False) -> F.SparseFormat:
    """Values-only refresh of a condensed(-over-active) leaf under UNCHANGED
    topology: re-gather ``weight * mask`` at the stored indices, reusing the
    indices (and out_index) arrays verbatim. See
    ``formats.Condensed.refresh_values`` for the exactness/donation contract.
    """
    if isinstance(leaf, dict):
        leaf = F.from_legacy_leaf(leaf, d_in=weight.shape[-2],
                                  d_out=weight.shape[-1])
    return leaf.refresh_values(weight, mask, donate=donate)


def export_condensed(cfg, registry, params: dict, masks: dict,
                     stats: dict[str, ExportStats] | None = None) -> dict:
    """Concrete export after training. k per stack = max realized fan-in.
    Leaves are ``formats.Condensed`` objects."""
    return _export_tree(F.Condensed, registry, params, masks, stats)


def export_condensed_over_active(cfg, registry, params: dict, masks: dict,
                                 stats: dict[str, ExportStats] | None = None) -> dict:
    """Composed export: ablated neurons dropped, survivors condensed
    (``formats.CondensedOverActive`` leaves — the paper's combined Fig. 4
    point, token-identical to masked for ANY mask)."""
    return _export_tree(F.CondensedOverActive, registry, params, masks, stats)


def export_structured(cfg, registry, masks: dict,
                      stats: dict[str, ExportStats] | None = None) -> dict:
    """Structured-only serving pytree (Fig. 4 "structured"):
    ``formats.StructuredFanIn`` leaves — ablated output neurons dropped,
    active columns kept dense and executed by the column-gathered kernel
    (``active_index`` sized at each stack's realized active count, fetched
    with the registry-level fused stats sync)."""
    stats = stats if stats is not None else export_stats(registry, masks)
    out: dict = {}
    for s in registry:
        m = REG.get_path(masks, s.path)
        REG.set_path(out, s.path,
                     structured_stack_leaf(m, d_in=s.d_in,
                                           stats=stats[s.name]))
    return out


def _export_tree(cls, registry, params, masks, stats):
    stats = stats if stats is not None else export_stats(registry, masks)
    out: dict = {}
    for s in registry:
        w = REG.get_path(params, s.path)
        m = REG.get_path(masks, s.path)
        REG.set_path(out, s.path, cls.export_from_dense(w, m, stats[s.name]))
    return out


def abstract_condensed(cfg, registry, param_dtype=None) -> dict:
    """ShapeDtypeStruct stand-ins at the target fan-in (for the dry-run).
    Delegates to the plan subsystem's abstract tree (single leaf-schema
    definition); lazy import to avoid a module cycle."""
    from repro.sparse import plan as PLAN
    return PLAN.abstract_serving_tree(cfg, registry,
                                      {s.name: "condensed" for s in registry},
                                      param_dtype=param_dtype)


def condensed_bytes(cfg, registry) -> tuple[int, int]:
    """(condensed weight bytes, dense weight bytes) across sparse stacks."""
    dense = cond = 0
    itemsize = jnp.dtype(cfg.param_dtype).itemsize
    for s in registry:
        k = D.fan_in_from_density(s.d_in, s.density)
        n = s.n_replicas
        dense += n * s.d_in * s.d_out * itemsize
        cond += n * s.d_out * k * (itemsize + 4)  # values + int32 indices
    return cond, dense
