"""Ablation-aware kernels: column-gathered structured matmul + fused COA.

The acceptance criteria made executable:

* the structured Pallas kernel is BIT-identical to the
  ``ops.structured_dense`` reference on every edge case — zero ablation,
  all-but-one-ablated, non-tile-aligned active counts, bf16, batch 1 and
  block-straddling batches;
* the fused condensed-over-active kernel is bit-identical to the pre-fusion
  compose-then-scatter lowering (and therefore token-identical to masked);
* both ops have working backward passes matching the reference gradients;
* tuned blocks stored under the structured/coa tuning keys are consumed by
  the ops wrappers at trace time;
* the fused epilogue removes the standalone scatter op from the lowered
  decode program (HLO dispatch-count assertion via ``launch.hlo_analysis``);
* ``--path auto`` picks structured for ablation-only stacks at the batch
  the cost model predicts, with serving weight bytes below masked, and
  ``StructuredFanIn.estimate_weight_bytes`` scales ~linearly with the
  active fraction.
"""
import dataclasses
import typing

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _numerics import assert_sums_close, assert_zeros_where

from repro.core import topology
from repro.kernels import ops
from repro.kernels import structured_matmul as sm
from repro.sparse import autotune as AT
from repro.sparse import condensed as COND
from repro.sparse import formats as F
from repro.sparse import plan as PLAN


def _active_setup(b, d_in, d_out, a, dtype=jnp.float32, seed=0):
    """(x, w, padded active_index, neuron_active bools) with a random
    size-``a`` surviving set."""
    k1, k2, k3 = jax.random.split(jax.random.PRNGKey(seed), 3)
    x = jax.random.normal(k1, (b, d_in), jnp.float32).astype(dtype)
    w = jax.random.normal(k2, (d_in, d_out), jnp.float32).astype(dtype)
    ai = jnp.sort(jax.random.permutation(k3, d_out)[:a]).astype(jnp.int32)
    active = jnp.zeros((d_out,), bool).at[ai].set(True)
    a_pad = sm.padded_active_count(max(a, 1), d_out)
    ai_padded = jnp.pad(ai, (0, a_pad - a), constant_values=d_out)
    return x, w, ai_padded, active


# ---------------------------------------------------------------------------
# structured kernel: bit-identity vs the structured_dense reference
# ---------------------------------------------------------------------------

STRUCT_SHAPES = [
    # (b, d_in, d_out, a)
    (1, 64, 128, 37),      # decode, non-tile-aligned active count
    (4, 64, 128, 128),     # zero ablation (every neuron survives)
    (8, 32, 16, 1),        # all-but-one ablated
    (3, 32, 48, 0),        # fully ablated (output must be exact zeros)
    (130, 96, 257, 5),     # block-straddling batch, non-aligned d_out
    (256, 128, 300, 155),  # general tiled path
]


def _assert_matches_dense(y, y_ref, x, w, active):
    """Ablated columns are exact zeros; surviving columns are the same
    d_in-term dot as the reference, summed in the kernel's order (see
    tests/_numerics.py for the bound)."""
    assert_zeros_where(y, ~np.asarray(active)[None, :])
    abs_sum = np.abs(np.asarray(x, np.float32)) @ np.abs(np.asarray(w, np.float32))
    assert_sums_close(y, y_ref, abs_sum, x.shape[-1])


@pytest.mark.parametrize("b,d_in,d_out,a", STRUCT_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_structured_kernel_bit_identical_to_reference(b, d_in, d_out, a, dtype):
    x, w, ai, active = _active_setup(b, d_in, d_out, a, dtype=dtype,
                                     seed=b * 3 + a)
    y = sm.structured_matmul(x, w, ai)
    y_ref = ops.structured_dense(x, w, active)
    assert y.dtype == y_ref.dtype
    _assert_matches_dense(y, y_ref, x, w, active)


def test_structured_kernel_forced_blocks_padding_paths():
    """Shapes straddling both block boundaries, blocks forced one-sided and
    both-sided — ablated columns bitwise zero, the rest within the sum
    reordering bound of the reference."""
    x, w, ai, active = _active_setup(130, 40, 200, 77, seed=11)
    y_ref = ops.structured_dense(x, w, active)
    for kw in ({"block_b": 32, "block_n": 128}, {"block_b": 128},
               {"block_n": 128}):
        y = sm.structured_matmul(x, w, ai, **kw)
        _assert_matches_dense(y, y_ref, x, w, active)
    # the decode variant agrees with the general kernel at any batch it fits
    y_dec = sm.structured_matmul_decode(x, w, ai)
    _assert_matches_dense(y_dec, y_ref, x, w, active)


def test_structured_linear_grads_match_reference():
    """Custom VJP: dx/dw agree with differentiating the structured_dense
    reference (ablated columns receive zero weight gradient)."""
    x, w, ai, active = _active_setup(6, 24, 40, 13, seed=5)
    f = lambda x, w: jnp.sum(jnp.tanh(ops.structured_linear(x, w, ai)))
    g = lambda x, w: jnp.sum(jnp.tanh(ops.structured_dense(x, w, active)))
    gx1, gw1 = jax.grad(f, (0, 1))(x, w)
    gx2, gw2 = jax.grad(g, (0, 1))(x, w)
    np.testing.assert_allclose(np.array(gx1), np.array(gx2), atol=1e-5)
    np.testing.assert_allclose(np.array(gw1), np.array(gw2), atol=1e-5)
    # ablated columns: exact zero gradient
    assert np.all(np.array(gw1)[:, ~np.array(active)] == 0.0)


def test_structured_linear_nd_leading_dims():
    x, w, ai, active = _active_setup(1, 24, 40, 13, seed=7)
    x3 = jax.random.normal(jax.random.PRNGKey(8), (3, 5, 24))
    y = ops.structured_linear_nd(x3, w, ai)
    assert y.shape == (3, 5, 40)
    y2 = ops.structured_linear(x3.reshape(-1, 24), w, ai).reshape(3, 5, 40)
    np.testing.assert_array_equal(np.array(y), np.array(y2))


def test_structured_format_exports_and_applies_gathered_kernel():
    """StructuredFanIn built from an ablation-only mask: active_index sized
    at the realized count (lane-padded), apply exact vs masked."""
    d_in, d_out = 48, 256
    col_active = (jnp.arange(d_out) % 3) != 0
    mask = jnp.broadcast_to(col_active[None, :], (d_in, d_out))
    w = jax.random.normal(jax.random.PRNGKey(0), (d_in, d_out))
    fmt = F.StructuredFanIn.export_from_dense(w, mask)
    a = int(col_active.sum())
    assert fmt.active_index.shape[-1] == sm.padded_active_count(a, d_out)
    x = jax.random.normal(jax.random.PRNGKey(1), (4, d_in))
    np.testing.assert_array_equal(np.array(fmt.apply(x, w)),
                                  np.array(x @ (w * mask)))
    # legacy instance (pre-active_index pytrees): reference fallback path
    legacy = F.StructuredFanIn(neuron_active=col_active, d_in=d_in)
    assert legacy.tuning_key(1) is None
    np.testing.assert_allclose(np.array(legacy.apply(x, w)),
                               np.array(x @ (w * mask)), atol=1e-6)


# ---------------------------------------------------------------------------
# fused condensed-over-active kernel
# ---------------------------------------------------------------------------

def _coa_setup(b, d_in, d_out, frac, seed=0, dtype=jnp.float32):
    key = jax.random.PRNGKey(seed)
    k_fan = max(1, d_in // 6)
    mask = topology.random_constant_fan_in_mask(key, d_in, d_out, k_fan)
    if frac:
        cut = d_out - max(1, int(d_out * frac))
        mask = mask & (jnp.arange(d_out) < cut)[None, :]
    w = jax.random.normal(jax.random.fold_in(key, 1), (d_in, d_out),
                          jnp.float32).astype(dtype)
    fmt = F.CondensedOverActive.export_from_dense(w, mask)
    x = jax.random.normal(jax.random.fold_in(key, 2), (b, d_in),
                          jnp.float32).astype(dtype)
    return x, w, mask, fmt


@pytest.mark.parametrize("b,d_in,d_out,frac",
                         [(1, 64, 128, 0.5), (4, 96, 257, 0.25),
                          (130, 64, 96, 0.9), (2, 48, 64, 0.0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_coa_fused_bit_identical_to_unfused(b, d_in, d_out, frac, dtype):
    x, w, mask, fmt = _coa_setup(b, d_in, d_out, frac, seed=b, dtype=dtype)
    y_fused = ops.condensed_over_active_linear_nd(
        x, fmt.values.astype(dtype), fmt.indices, fmt.out_index, d_out)
    y_old = ops.condensed_over_active_linear_nd_unfused(
        x, fmt.values.astype(dtype), fmt.indices, fmt.out_index, d_out)
    np.testing.assert_array_equal(np.array(y_fused), np.array(y_old))
    if dtype == jnp.float32:
        np.testing.assert_allclose(np.array(y_fused), np.array(x @ (w * mask)),
                                   atol=1e-5)


def test_coa_fused_general_and_decode_variants_agree():
    x, w, mask, fmt = _coa_setup(5, 64, 200, 0.4, seed=3)
    args = (fmt.values, fmt.indices, fmt.out_index, 200)
    y_dec = sm.condensed_over_active_matmul_decode(x, *args)
    y_gen = sm.condensed_over_active_matmul(x, *args, block_b=32, block_n=128)
    np.testing.assert_array_equal(np.array(y_dec), np.array(y_gen))


def test_coa_fused_grads_match_unfused():
    x, w, mask, fmt = _coa_setup(6, 48, 96, 0.5, seed=4)
    f = lambda x, v: jnp.sum(jnp.tanh(ops.condensed_over_active_linear_nd(
        x, v, fmt.indices, fmt.out_index, 96)))
    g = lambda x, v: jnp.sum(jnp.tanh(
        ops.condensed_over_active_linear_nd_unfused(
            x, v, fmt.indices, fmt.out_index, 96)))
    gx1, gv1 = jax.grad(f, (0, 1))(x, fmt.values)
    gx2, gv2 = jax.grad(g, (0, 1))(x, fmt.values)
    np.testing.assert_allclose(np.array(gx1), np.array(gx2), atol=1e-5)
    np.testing.assert_allclose(np.array(gv1), np.array(gv2), atol=1e-5)


# ---------------------------------------------------------------------------
# tuned-block consumption (structured + coa key spaces)
# ---------------------------------------------------------------------------

@pytest.fixture
def tmp_cache(tmp_path, monkeypatch):
    path = tmp_path / "autotune.json"
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(path))
    AT.reset_cache_state()
    yield path
    AT.reset_cache_state()


def test_structured_ops_consume_tuned_blocks(tmp_cache, monkeypatch):
    """structured_linear resolves its blocks from the autotune cache under
    the kind="structured" key at trace time."""
    b, d_in, a_pad, d_out = 1, 48, 128, 160
    res = AT.autotune_structured_blocks(b, d_in, a_pad, d_out, reps=2)
    assert res.speedup_vs_default >= 1.0
    assert "/structured-o" in res.key
    seen = {}
    orig_general, orig_decode = sm.structured_matmul, sm.structured_matmul_decode

    def spy_general(x, w, ai, **kw):
        seen.update(kw)
        return orig_general(x, w, ai, **kw)

    def spy_decode(x, w, ai, **kw):
        seen.update(kw, decode=True)
        return orig_decode(x, w, ai, **kw)

    monkeypatch.setattr(sm, "structured_matmul", spy_general)
    monkeypatch.setattr(sm, "structured_matmul_decode", spy_decode)

    x, w, ai, active = _active_setup(b, d_in, d_out, 100, seed=1)
    assert ai.shape[0] == a_pad
    y = ops.structured_linear(x, w, ai)
    np.testing.assert_array_equal(np.array(y),
                                  np.array(ops.structured_dense(x, w, active)))
    assert seen["block_b"] == res.block_b
    assert seen["block_n"] == res.block_n


def test_coa_ops_consume_tuned_blocks(tmp_cache, monkeypatch):
    b, d_in, a, k, d_out = 1, 48, 64, 8, 96   # k = _coa_setup's d_in // 6
    res = AT.autotune_coa_blocks(b, d_in, a, k, d_out, reps=2)
    assert res.speedup_vs_default >= 1.0
    assert "/coa-o" in res.key
    seen = {}
    orig = sm.condensed_over_active_matmul

    def spy(x, v, i, o, d, **kw):
        seen.update(kw)
        return orig(x, v, i, o, d, **kw)

    monkeypatch.setattr(sm, "condensed_over_active_matmul", spy)
    x, w, mask, fmt = _coa_setup(b, d_in, d_out, 0.34, seed=2)
    assert fmt.values.shape == (a, k), "setup must hit the tuned shape"
    y = ops.condensed_over_active_linear_nd(x, fmt.values, fmt.indices,
                                            fmt.out_index, d_out)
    np.testing.assert_allclose(np.array(y), np.array(x @ (w * mask)),
                               atol=1e-5)
    assert seen["block_b"] == res.block_b
    assert seen["block_n"] == res.block_n


# ---------------------------------------------------------------------------
# HLO dispatch-count: the fused epilogue removes the standalone scatter
# ---------------------------------------------------------------------------

def _scatter_count(hlo_text: str) -> int:
    """Standalone-scatter dispatches in an optimized HLO module.

    Counted via launch.hlo_analysis's instruction parse. The CPU backend's
    ScatterExpander rewrites scatter ops into while loops before scheduling,
    so besides literal ``scatter`` ops we also count instructions whose
    op_name metadata traces back to a jnp scatter (the metadata survives the
    expansion; a TPU lowering keeps the scatter op itself)."""
    import re

    from repro.launch import hlo_analysis as HLO
    comps = HLO.parse_hlo(hlo_text)
    return sum(
        1 for c in comps.values() for i in c.instructions
        if i.op == "scatter"
        or re.search(r'op_name="[^"]*scatter[^"]*"', i.attrs))


def test_unfused_coa_lowering_contains_scatter_control():
    """Control for the dispatch-count assertion: the pre-fusion lowering DOES
    contain a standalone scatter op (so a zero count below is meaningful)."""
    x, w, mask, fmt = _coa_setup(2, 32, 64, 0.5, seed=6)
    hlo = jax.jit(
        lambda x, v, i, o: ops.condensed_over_active_linear_nd_unfused(
            x, v, i, o, 64)
    ).lower(x, fmt.values, fmt.indices, fmt.out_index).compile().as_text()
    assert _scatter_count(hlo) >= 1


def test_fused_coa_decode_program_has_no_standalone_scatter():
    """The engine's decode program under a condensed-over-active serving tree
    lowers WITHOUT any scatter the masked program doesn't also have (the
    epilogue's one-hot matmul replaced the y.at[:, out_index].add dispatch)."""
    from repro import configs
    from repro.models import model as M
    from repro.sparse import registry as REG

    cfg = configs.get_smoke_config("qwen3-1.7b")
    key = jax.random.PRNGKey(0)
    reg = REG.build_registry(cfg)
    params = M.init_params(cfg, key, REG.k_fan_map(cfg, reg))
    masks = REG.init_sparsity_state(cfg, key, reg)["masks"]
    # ablate a quarter of each stack's neurons so COA has rows to drop
    abl = {}
    for s in reg:
        m = REG.get_path(masks, s.path)
        cut = s.d_out - max(1, s.d_out // 4)
        REG.set_path(abl, s.path, m & (jnp.arange(s.d_out) < cut)[None, :])
    tree = COND.export_condensed_over_active(cfg, reg, params, abl)

    batch = {"tokens": jnp.zeros((2, 1), jnp.int32)}
    cache = M.init_cache(cfg, 2, max_len=8)

    def lower(serving_tree):
        return jax.jit(
            lambda p, m, b, c: M.decode_step(cfg, p, m, b, c)
        ).lower(params, serving_tree, batch, cache).compile().as_text()

    n_coa = _scatter_count(lower(tree))
    n_masked = _scatter_count(lower(abl))
    assert n_coa == n_masked, (
        f"fused COA decode has {n_coa} scatter op(s) vs masked baseline "
        f"{n_masked} — the standalone out_index scatter is back")


# ---------------------------------------------------------------------------
# plan: structured competes (and wins) in auto for ablation-only stacks
# ---------------------------------------------------------------------------

def _ablation_only_masks(reg, masks, frac):
    import repro.sparse.registry as REG
    out = {}
    for s in reg:
        m = REG.get_path(masks, s.path)
        cut = s.d_out - max(1, int(s.d_out * frac))
        col = (jnp.arange(s.d_out) < cut)[None, :]
        REG.set_path(out, s.path, jnp.broadcast_to(col, m.shape))
    return out


@pytest.fixture(scope="module")
def wide_ablation_setup():
    """Smoke config with a roofline-ish d_ff so the lane-padded active count
    leaves room for structured to win (the 64/128-wide smoke stacks pad any
    active count up to a full 128 lanes)."""
    from repro import configs
    from repro.models import model as M
    from repro.sparse import registry as REG

    cfg = configs.get_smoke_config("qwen3-1.7b").replace(d_ff=1024)
    key = jax.random.PRNGKey(0)
    reg = REG.build_registry(cfg)
    params = M.init_params(cfg, key, REG.k_fan_map(cfg, reg))
    masks = REG.init_sparsity_state(cfg, key, reg)["masks"]
    abl_only = _ablation_only_masks(reg, masks, 0.5)
    return cfg, reg, params, abl_only


def test_auto_selects_structured_for_ablation_only_stacks(wide_ablation_setup):
    cfg, reg, params, abl_only = wide_ablation_setup
    plan = PLAN.build_plan(cfg, reg, params, abl_only, batch_size=1,
                           path="auto")
    wide = [s.name for s in reg if s.d_out >= 512]
    assert wide, "setup must contain roofline-width stacks"
    for name in wide:
        assert plan.representation_of(name) == "structured", (
            name, plan.decisions[name].est_s)
    # the structured plan's weight traffic undercuts the masked reference
    serving, masked_ref = plan.weight_bytes()
    assert serving < masked_ref
    # exactness: the planned tree decodes identically to masked (per-stack
    # leaves only chosen among exact representations)
    stats = COND.export_stats(reg, abl_only)
    for name in wide:
        assert stats[name].min_fan_in == [s for s in reg
                                          if s.name == name][0].d_in


def test_auto_structured_crossover_lands_in_predicted_bucket():
    """The cost model predicts a structured->masked crossover batch for an
    ablation-only stack whose scatter epilogue outweighs the column saving
    at large batch; auto flips representation inside the SAME batch bucket
    (the kernel_autotune.py bucket methodology)."""
    import types
    stack = types.SimpleNamespace(name="t", d_in=1024, d_out=1024,
                                  n_replicas=1)
    stats = F.ExportStats(k=1024, max_active=896, active_fraction=0.875,
                          min_fan_in=1024)

    def rep_at(b):
        return PLAN.select_representation(
            stack, batch_size=b, itemsize=4, stats=stats).representation

    assert rep_at(1) == "structured"          # bandwidth-bound decode
    assert rep_at(4096) == "masked"           # MXU wins back at large batch
    # binary-search the model's crossover, then assert the decision flips
    # within that batch's bucket (same-bucket contract as kernel_autotune)
    lo, hi = 1, 4096
    while hi - lo > 1:
        mid = (lo + hi) // 2
        if rep_at(mid) == "masked":
            hi = mid
        else:
            lo = mid
    bucket = AT.batch_bucket(hi)
    assert rep_at(bucket) == "masked"
    prev_bucket_top = max(b for b in AT.BATCH_BUCKETS if b < bucket) \
        if bucket > AT.BATCH_BUCKETS[0] else 1
    assert rep_at(max(prev_bucket_top, lo)) in ("structured", "masked")
    assert rep_at(min(lo, prev_bucket_top)) == "structured"


def test_structured_weight_bytes_scale_linearly_with_active_fraction():
    """estimate_weight_bytes ~ active_fraction at lane-aligned counts (the
    128-lane export padding is the only quantization)."""
    d_in, d_out = 3072, 1024
    full = None
    for a in (128, 256, 512, 768, 1024):
        spec = F.FormatSpec(d_in=d_in, d_out=d_out, n_replicas=1, itemsize=4,
                            k=d_in, max_active=a, active_fraction=a / d_out)
        got = F.StructuredFanIn.estimate_weight_bytes(spec)
        if full is None:
            full = got * d_out / a  # extrapolated full-width bytes
        assert got == pytest.approx(full * a / d_out, rel=1e-6)
    # and the full-width gathered panel undercuts masked (no mask byte read)
    spec1 = F.FormatSpec(d_in=d_in, d_out=d_out, n_replicas=1, itemsize=4,
                         k=d_in, max_active=d_out, active_fraction=1.0)
    assert (F.StructuredFanIn.estimate_weight_bytes(spec1)
            < F.MaskedDense.estimate_weight_bytes(spec1))


def test_auto_still_never_selects_structured_for_fine_grained_masks():
    """min_fan_in < d_in (fine-grained sparsity, even with ablation) keeps
    structured out of the candidate set — it would not be exact."""
    import types
    stack = types.SimpleNamespace(name="t", d_in=1024, d_out=1024,
                                  n_replicas=1)
    stats = F.ExportStats(k=102, max_active=512, active_fraction=0.5,
                          min_fan_in=102)
    for b in (1, 8, 64, 512):
        dec = PLAN.select_representation(stack, batch_size=b, itemsize=4,
                                         stats=stats)
        assert dec.representation != "structured"


# ---------------------------------------------------------------------------
# checkpoint: archives predating active_index rebuild it from restored bools
# ---------------------------------------------------------------------------

class _State(typing.NamedTuple):
    step: jnp.int32
    serve: dict


def test_checkpoint_restore_rebuilds_missing_active_index(tmp_path):
    from repro.train import checkpoint as CKPT

    d_in, d_out = 16, 192
    col_active = (jnp.arange(d_out) % 5) != 0
    mask = jnp.broadcast_to(col_active[None, :], (d_in, d_out))
    w = jax.random.normal(jax.random.PRNGKey(0), (d_in, d_out))
    fmt = F.StructuredFanIn.export_from_dense(w, mask)

    # archive written by a pre-active_index layout: only neuron_active saved
    legacy = _State(step=jnp.int32(1),
                    serve={"stack": {"neuron_active": fmt.neuron_active}})
    path = CKPT.save(str(tmp_path), legacy)
    assert path

    template = _State(step=jnp.int32(0),
                      serve={"stack": dataclasses.replace(
                          fmt,
                          neuron_active=jnp.zeros_like(fmt.neuron_active),
                          active_index=jnp.zeros_like(fmt.active_index))})
    restored = CKPT.restore(str(tmp_path), 1, template)
    got = restored.serve["stack"]
    np.testing.assert_array_equal(np.array(got.neuron_active),
                                  np.array(fmt.neuron_active))
    # active_index was NOT in the archive: rebuilt from the restored bools,
    # not left at the template's zeros
    np.testing.assert_array_equal(np.array(got.active_index),
                                  np.array(fmt.active_index))
    x = jax.random.normal(jax.random.PRNGKey(1), (2, d_in))
    np.testing.assert_array_equal(np.array(got.apply(x, w)),
                                  np.array(x @ (w * mask)))


def test_checkpoint_rebuild_resizes_when_archive_has_more_actives(tmp_path):
    """The rebuilt active_index is sized from the RESTORED bools' realized
    active count — a template whose vector was sized from sparser masks must
    not silently truncate (and thereby zero) the archive's extra actives."""
    from repro.train import checkpoint as CKPT

    d_in, d_out = 8, 512
    # archive: 384 active columns; template: sized for only 128
    arch_active = jnp.arange(d_out) < 384
    arch_mask = jnp.broadcast_to(arch_active[None, :], (d_in, d_out))
    tmpl_mask = jnp.broadcast_to((jnp.arange(d_out) < 128)[None, :],
                                 (d_in, d_out))
    w = jax.random.normal(jax.random.PRNGKey(0), (d_in, d_out))
    tmpl = F.StructuredFanIn.export_from_dense(w, tmpl_mask)
    assert tmpl.active_index.shape[-1] == 128

    legacy = _State(step=jnp.int32(1),
                    serve={"stack": {"neuron_active": arch_active}})
    CKPT.save(str(tmp_path), legacy)
    got = CKPT.restore(str(tmp_path), 1, _State(step=jnp.int32(0),
                                                serve={"stack": tmpl})).serve["stack"]
    assert got.active_index.shape[-1] == sm.padded_active_count(384, d_out)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, d_in))
    np.testing.assert_array_equal(np.array(got.apply(x, w)),
                                  np.array(x @ (w * arch_mask)))
