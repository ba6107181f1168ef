"""Quantized condensed decode: int8/fp8 values with per-neuron scales.

The PR's acceptance criteria made executable:

* quantize/dequantize round-trip error stays within the documented per-dtype
  bound (int8: half a quantization step; fp8-e4m3: ~2^-4 relative);
* every quantized format's ``apply`` matches the scale-after-sum reference
  EXACTLY (float-associativity atol) and the f32 oracle within the
  quantization bound — the kernel adds no error of its own;
* int8 condensed streams <= 0.35x the HBM value bytes of f32 condensed at
  the benchmark decode fan-ins (k=13, k=26), priced via
  ``estimate_values_bytes`` AND measured from the exported arrays' nbytes;
* quantized tuning keys carry a ``wint8``/``wfp8`` width tag while float
  keys keep the byte-identical legacy ``w{bits}`` layout;
* the scalar-prefetch decode variant removes the hoisted XLA column gather
  (HLO dispatch count on the ``hoisted_column_gather`` scope tag);
* the out-blocked scatter epilogue (``block_o``) is bit-identical to the
  unblocked one;
* checkpoint round-trips both ways: a pre-quantization f32 archive restores
  into a quantized template (scales rebuilt), a quantized archive restores
  into an f32 template (dequantized);
* plans built with ``values_dtype`` export quantized leaves, price the real
  byte width, and ``refresh`` preserves the precision.
"""
import dataclasses
import re
import typing

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import topology
from repro.kernels import condensed_matmul as cm
from repro.kernels import structured_matmul as sm
from repro.sparse import formats as F

D_IN, D_OUT, K = 32, 48, 5
HAS_FP8 = "fp8" in F.VALUES_DTYPES
QDTYPES = ("int8",) + (("fp8",) if HAS_FP8 else ())
# documented relative-error bounds (Frobenius norm) for quantized apply vs
# the f32 oracle: int8 step = amax/127 (rel RMS ~0.7% on gaussian weights),
# e4m3 half-ulp = 2^-4 relative (~3.6% RMS) — bounds leave ~4x headroom
ORACLE_REL = {"int8": 0.03, "fp8": 0.15}


@pytest.fixture(scope="module")
def wm():
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (D_IN, D_OUT), jnp.float32)
    mask = topology.random_constant_fan_in_mask(
        jax.random.fold_in(key, 1), D_IN, D_OUT, K)
    cut = D_OUT - D_OUT // 4
    abl = mask & (jnp.arange(D_OUT) < cut)[None, :]
    abl_only = jnp.broadcast_to((jnp.arange(D_OUT) < cut)[None, :],
                                (D_IN, D_OUT))
    return w, mask, abl, abl_only


def _rel(a, b):
    return float(np.linalg.norm(np.asarray(a) - np.asarray(b))
                 / max(np.linalg.norm(np.asarray(b)), 1e-12))


# ---------------------------------------------------------------------------
# quantize/dequantize round-trip bounds
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("qdt", QDTYPES)
def test_quantize_roundtrip_within_documented_bound(qdt):
    v = jax.random.normal(jax.random.PRNGKey(3), (16, 7), jnp.float32)
    q, s = F.quantize_values(v, qdt)
    assert q.dtype == jnp.dtype(F.VALUES_DTYPES[qdt])
    assert s.dtype == jnp.float32 and s.shape == (16,)
    deq = F.dequantize_values(q, s)
    err = np.abs(np.asarray(deq) - np.asarray(v))
    scol = np.asarray(s)[:, None]
    if qdt == "int8":
        # symmetric rounding: at most half a quantization step per element
        bound = scol * (0.5 + 1e-3)
    else:
        # e4m3: half-ulp relative error for normals + a subnormal floor
        bound = np.abs(np.asarray(v)) * 2.0**-4 + scol * 2.0**-6
    assert (err <= bound).all(), float((err - bound).max())


def test_quantize_all_zero_rows_get_unit_scale():
    v = jnp.zeros((4, 6), jnp.float32)
    q, s = F.quantize_values(v, "int8")
    np.testing.assert_array_equal(np.asarray(s), np.ones(4, np.float32))
    np.testing.assert_array_equal(np.asarray(q), np.zeros((4, 6), np.int8))


# ---------------------------------------------------------------------------
# format apply: exact vs scale-after-sum reference, bounded vs f32 oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("qdt", QDTYPES)
def test_condensed_quantized_apply_exact_and_bounded(wm, qdt):
    w, mask = wm[0], wm[1]
    fmt = F.Condensed.export_from_dense(w, mask, quantize_spec=qdt)
    assert fmt.values_dtype == qdt and fmt.scales is not None
    x = jax.random.normal(jax.random.PRNGKey(4), (2, D_IN))
    y = fmt.apply(x, w)
    # scale-after-sum reference: the kernel's exact contract
    deq = F.dequantize_values(fmt.values, fmt.scales)
    xg = jnp.take(x, fmt.indices, axis=1)            # (B, d_out, k)
    y_ref = (xg * deq[None]).sum(-1)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-4)
    # f32 oracle within the quantization bound (kernel adds no error)
    assert _rel(y, x @ (w * mask)) <= ORACLE_REL[qdt]


@pytest.mark.parametrize("qdt", QDTYPES)
def test_coa_quantized_apply_exact_and_bounded(wm, qdt):
    w, abl = wm[0], wm[2]
    fmt = F.CondensedOverActive.export_from_dense(w, abl, quantize_spec=qdt)
    assert fmt.values_dtype == qdt and fmt.scales is not None
    x = jax.random.normal(jax.random.PRNGKey(5), (2, D_IN))
    y = fmt.apply(x, w)
    deq = np.asarray(F.dequantize_values(fmt.values, fmt.scales))
    xg = np.take(np.asarray(x), np.asarray(fmt.indices), axis=1)
    compact = (xg * deq[None]).sum(-1)               # (B, a)
    oi = np.asarray(fmt.out_index)
    y_ref = np.zeros((2, D_OUT), np.float32)
    valid = oi < D_OUT
    y_ref[:, oi[valid]] = compact[:, valid]
    np.testing.assert_allclose(np.asarray(y), y_ref, atol=1e-4)
    assert _rel(y, x @ (w * abl)) <= ORACLE_REL[qdt]


@pytest.mark.parametrize("qdt", QDTYPES)
def test_structured_quantized_apply_bounded(wm, qdt):
    w, abl_only = wm[0], wm[3]
    fmt = F.StructuredFanIn.export_from_dense(w, abl_only, quantize_spec=qdt)
    assert fmt.values_dtype == qdt and fmt.scales is not None
    assert fmt.values.dtype == jnp.dtype(F.VALUES_DTYPES[qdt])
    x = jax.random.normal(jax.random.PRNGKey(6), (2, D_IN))
    y = fmt.apply(x, w)
    assert _rel(y, x @ (w * abl_only)) <= ORACLE_REL[qdt]


def test_float_quantize_spec_keeps_float_values_no_scales(wm):
    w, mask = wm[0], wm[1]
    for spec, dt in ((None, jnp.float32), ("f32", jnp.float32),
                     ("bf16", jnp.bfloat16)):
        fmt = F.Condensed.export_from_dense(w, mask, quantize_spec=spec)
        assert fmt.values.dtype == dt and fmt.scales is None


# ---------------------------------------------------------------------------
# kernels: dequant-fused matmuls match the scale-after-sum jnp reference
# ---------------------------------------------------------------------------

def _condensed_operands(b, d_in, n_out, k, seed=7):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (b, d_in), jnp.float32)
    v = jax.random.normal(jax.random.fold_in(key, 1), (n_out, k))
    idx = jax.random.randint(jax.random.fold_in(key, 2), (n_out, k), 0, d_in)
    q, s = F.quantize_values(v, "int8")
    y_ref = (jnp.take(x, idx, axis=1)
             * F.dequantize_values(q, s)[None]).sum(-1)
    return x, q, idx, s, y_ref


def test_condensed_matmul_decode_scaled_matches_reference():
    x, q, idx, s, y_ref = _condensed_operands(2, 64, 128, 13)
    y = cm.condensed_matmul_decode(x, q, idx, scales=s, interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-5)


def test_condensed_matmul_tiled_scaled_matches_reference():
    x, q, idx, s, y_ref = _condensed_operands(32, 64, 128, 13)
    y = cm.condensed_matmul(x, q, idx, scales=s, block_b=8, block_n=64,
                            interpret=True)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref), atol=1e-5)


def _coa_operands(b, d_in, d_out, seed=8):
    key = jax.random.PRNGKey(seed)
    w = jax.random.normal(key, (d_in, d_out))
    col = (jnp.arange(d_out) % 4) != 0
    mask = jnp.broadcast_to(col[None, :], (d_in, d_out))
    fmt = F.CondensedOverActive.export_from_dense(w, mask,
                                                  quantize_spec="int8")
    x = jax.random.normal(jax.random.fold_in(key, 1), (b, d_in))
    return x, fmt, np.asarray(x @ (w * mask))


@pytest.mark.parametrize("b", [2, 32])
def test_coa_matmul_scaled_matches_oracle_within_bound(b):
    x, fmt, oracle = _coa_operands(b, 32, 96)
    y = sm.condensed_over_active_matmul(
        x, fmt.values, fmt.indices, fmt.out_index, fmt.d_out,
        scales=fmt.scales, interpret=True,
        **({} if b <= 8 else {"block_b": 8, "block_n": 64}))
    assert _rel(y, oracle) <= ORACLE_REL["int8"]


# ---------------------------------------------------------------------------
# out-blocked epilogue: bit-identical to the unblocked scatter
# ---------------------------------------------------------------------------

def _structured_setup(b, d_in, d_out, seed=9):
    key = jax.random.PRNGKey(seed)
    w = jax.random.normal(key, (d_in, d_out))
    col = (jnp.arange(d_out) % 3) != 0
    fmt = F.StructuredFanIn.export_from_dense(
        w, jnp.broadcast_to(col[None, :], (d_in, d_out)))
    x = jax.random.normal(jax.random.fold_in(key, 1), (b, d_in))
    return x, w, fmt.active_index


def test_structured_decode_block_o_bit_identical():
    x, w, ai = _structured_setup(2, 32, 256)
    base = sm.structured_matmul_decode(x, w, ai, interpret=True,
                                       prefetch_gather=False)
    tiled = sm.structured_matmul_decode(x, w, ai, block_o=128,
                                        interpret=True,
                                        prefetch_gather=False)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(tiled))


def test_structured_tiled_block_o_bit_identical():
    x, w, ai = _structured_setup(32, 32, 256)
    base = sm.structured_matmul(x, w, ai, block_b=8, block_n=128,
                                interpret=True)
    tiled = sm.structured_matmul(x, w, ai, block_b=8, block_n=128,
                                 block_o=128, interpret=True)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(tiled))


@pytest.mark.parametrize("b", [2, 32])
def test_coa_block_o_bit_identical(b):
    x, fmt, _ = _coa_operands(b, 32, 256)
    kw = {} if b <= 8 else {"block_b": 8, "block_n": 64}
    base = sm.condensed_over_active_matmul(
        x, fmt.values, fmt.indices, fmt.out_index, fmt.d_out,
        scales=fmt.scales, interpret=True, **kw)
    tiled = sm.condensed_over_active_matmul(
        x, fmt.values, fmt.indices, fmt.out_index, fmt.d_out,
        scales=fmt.scales, block_o=128, interpret=True, **kw)
    np.testing.assert_array_equal(np.asarray(base), np.asarray(tiled))


# ---------------------------------------------------------------------------
# scalar-prefetch gather: HLO dispatch count + numerics
# ---------------------------------------------------------------------------

def _gather_count(hlo_text: str) -> int:
    """Occurrences of the ``hoisted_column_gather`` scope tag in op_name
    metadata — the ONE XLA gather pass the decode scan hoists (see
    structured_matmul._gather_columns). The scalar-prefetch variant performs
    the gather in-kernel, so its program must not carry the tag at all."""
    return len(re.findall(r'op_name="[^"]*hoisted_column_gather', hlo_text))


def test_prefetch_gather_removes_hoisted_column_gather_from_hlo():
    x, w, ai = _structured_setup(2, 16, 128)

    def lower(prefetch):
        return jax.jit(
            lambda x, w, ai: sm.structured_matmul_decode(
                x, w, ai, interpret=True, prefetch_gather=prefetch)
        ).lower(x, w, ai).compile().as_text()

    assert _gather_count(lower(False)) >= 1   # control: the hoist is there
    assert _gather_count(lower(True)) == 0    # prefetch: moved in-kernel


def test_prefetch_gather_matches_hoisted_variant():
    x, w, ai = _structured_setup(2, 16, 128)
    hoisted = sm.structured_matmul_decode(x, w, ai, interpret=True,
                                          prefetch_gather=False)
    prefetched = sm.structured_matmul_decode(x, w, ai, interpret=True,
                                             prefetch_gather=True)
    np.testing.assert_allclose(np.asarray(hoisted), np.asarray(prefetched),
                               atol=1e-5)


def test_prefetch_env_flag_default_off(monkeypatch):
    monkeypatch.delenv("REPRO_PREFETCH_GATHER", raising=False)
    assert sm._prefetch_default() is False
    monkeypatch.setenv("REPRO_PREFETCH_GATHER", "1")
    assert sm._prefetch_default() is True
    monkeypatch.setenv("REPRO_PREFETCH_GATHER", "0")
    assert sm._prefetch_default() is False


# ---------------------------------------------------------------------------
# VMEM cap override
# ---------------------------------------------------------------------------

def test_vmem_cap_env_override(monkeypatch):
    monkeypatch.setenv("REPRO_VMEM_CAP_BYTES", "123456")
    # the usable fraction still applies on top of the overridden cap
    # (double-buffering headroom — see the vmem_budget_bytes docstring)
    assert cm.vmem_budget_bytes() == int(123456 * cm.VMEM_USABLE_FRACTION)
    monkeypatch.delenv("REPRO_VMEM_CAP_BYTES")
    assert cm.vmem_budget_bytes() != int(123456 * cm.VMEM_USABLE_FRACTION)


def test_vmem_tiny_cap_keeps_minimum_block(monkeypatch):
    # documented stance: the (8, 128) minimum is kept even over budget
    monkeypatch.setenv("REPRO_VMEM_CAP_BYTES", "4096")
    cands = cm.block_candidates(8, 64, 128, 13)
    assert (8, 128) in cands


# ---------------------------------------------------------------------------
# tuning keys: quantized width tags, float keys byte-identical legacy
# ---------------------------------------------------------------------------

def test_tuning_key_float_layout_unchanged():
    key = F.shape_tuning_key(64, 128, 13, 1, backend="cpu", itemsize=4)
    assert key == "cpu/w32/d64/n128/k13/b1"
    key16 = F.shape_tuning_key(64, 128, 13, 1, backend="cpu", itemsize=2)
    assert key16 == "cpu/w16/d64/n128/k13/b1"
    # "f32" spelled explicitly resolves to the same legacy key as None
    assert F.shape_tuning_key(64, 128, 13, 1, backend="cpu", itemsize=4,
                              values_dtype="f32") == key


def test_tuning_key_quantized_width_tag():
    key = F.shape_tuning_key(64, 128, 13, 1, backend="cpu", itemsize=4,
                             values_dtype="int8")
    assert key == "cpu/wint8/d64/n128/k13/b1"
    if HAS_FP8:
        key8 = F.shape_tuning_key(64, 128, 13, 1, backend="cpu", itemsize=4,
                                  values_dtype="fp8")
        assert key8 == "cpu/wfp8/d64/n128/k13/b1"
        assert key8 != key  # same byte width, distinct key spaces


def test_quantized_leaf_tuning_key_tagged(wm):
    w, mask = wm[0], wm[1]
    fmt = F.Condensed.export_from_dense(w, mask, quantize_spec="int8")
    assert "/wint8/" in fmt.tuning_key(1)
    f32 = F.Condensed.export_from_dense(w, mask)
    assert "/w32/" in f32.tuning_key(1)


# ---------------------------------------------------------------------------
# autotune: quantized smoke — tuned never slower than default, key tagged
# ---------------------------------------------------------------------------

def test_autotune_quantized_smoke():
    from repro.sparse import autotune as AT
    res = AT.autotune_blocks(1, 64, 128, 13, reps=1, values_dtype="int8",
                             save=False)
    assert "/wint8/" in res.key
    assert res.us <= res.default_us  # the default is IN the measured table
    assert res.table


# ---------------------------------------------------------------------------
# 0.35x acceptance: int8 value stream vs f32, priced AND measured
# ---------------------------------------------------------------------------

class _Shape(typing.NamedTuple):
    d_in: int
    d_out: int


@pytest.mark.parametrize("d_in,d_out,k", [(64, 128, 13), (128, 256, 26)])
def test_int8_value_stream_at_most_035x_of_f32(d_in, d_out, k):
    """The PR's headline number at the benchmark decode fan-ins: int8 values
    + f32 per-neuron scales stream <= 0.35x the bytes of f32 values —
    (k + 4) / (4k), so it needs k >= 10 (documented in the benchmark)."""
    stats = F.ExportStats(k=k, max_active=d_out, active_fraction=1.0)
    shape = _Shape(d_in, d_out)
    priced_q = F.Condensed.estimate_values_bytes(
        F.spec_for_stack(shape, stats, 4, "int8"))
    priced_f = F.Condensed.estimate_values_bytes(
        F.spec_for_stack(shape, stats, 4))
    assert priced_q / priced_f <= 0.35
    assert priced_q / priced_f == (k + 4) / (4 * k)

    mask = topology.random_constant_fan_in_mask(
        jax.random.PRNGKey(0), d_in, d_out, k)
    w = jax.random.normal(jax.random.PRNGKey(1), (d_in, d_out))
    leaf_q = F.Condensed.export_from_dense(w, mask, quantize_spec="int8")
    leaf_f = F.Condensed.export_from_dense(w, mask)
    measured_q = leaf_q.values.nbytes + leaf_q.scales.nbytes
    measured_f = leaf_f.values.nbytes
    assert measured_q / measured_f <= 0.35
    # priced == measured: the estimator prices exactly what export allocates
    assert measured_q == priced_q and measured_f == priced_f


# ---------------------------------------------------------------------------
# checkpoint round-trips: f32 archive <-> quantized template
# ---------------------------------------------------------------------------

class _State(typing.NamedTuple):
    step: jnp.int32
    serve: dict


def test_checkpoint_f32_archive_restores_into_quantized_template(
        wm, tmp_path):
    """A pre-quantization archive (float values, no scales) restores into an
    int8 template: the restored float values are quantized and the missing
    scales rebuilt — NOT left at the template's (wrong) scales."""
    from repro.train import checkpoint as CKPT

    w, mask = wm[0], wm[1]
    f32 = F.Condensed.export_from_dense(w, mask)
    CKPT.save(str(tmp_path), _State(step=jnp.int32(1),
                                    serve={"stack": f32}))

    # template exported from DIFFERENT weights so its scales are wrong on
    # purpose — the restore must re-derive them from the archive's values
    w2 = jax.random.normal(jax.random.PRNGKey(7), (D_IN, D_OUT))
    tmpl = F.Condensed.export_from_dense(w2, mask, quantize_spec="int8")
    got = CKPT.restore(str(tmp_path), 1,
                       _State(step=jnp.int32(0),
                              serve={"stack": tmpl})).serve["stack"]
    assert got.values_dtype == "int8"
    assert got.values.dtype == jnp.int8 and got.scales is not None
    q, s = F.quantize_values(f32.values, "int8")
    np.testing.assert_array_equal(np.asarray(got.values), np.asarray(q))
    np.testing.assert_allclose(np.asarray(got.scales), np.asarray(s))
    x = jax.random.normal(jax.random.PRNGKey(8), (2, D_IN))
    assert _rel(got.apply(x, w), x @ (w * mask)) <= ORACLE_REL["int8"]


def test_checkpoint_quantized_archive_restores_into_f32_template(
        wm, tmp_path):
    """The reverse direction: a quantized archive restores into a float
    template by dequantizing through the ADOPTED scales (a blind astype
    would reinterpret int8 codes as floats)."""
    from repro.train import checkpoint as CKPT

    w, mask = wm[0], wm[1]
    qfmt = F.Condensed.export_from_dense(w, mask, quantize_spec="int8")
    CKPT.save(str(tmp_path), _State(step=jnp.int32(2),
                                    serve={"stack": qfmt}))

    tmpl = F.Condensed.export_from_dense(
        jnp.zeros((D_IN, D_OUT), jnp.float32), mask)
    got = CKPT.restore(str(tmp_path), 2,
                       _State(step=jnp.int32(0),
                              serve={"stack": tmpl})).serve["stack"]
    assert got.values_dtype is None
    assert got.values.dtype == jnp.float32 and got.scales is None
    np.testing.assert_allclose(
        np.asarray(got.values),
        np.asarray(F.dequantize_values(qfmt.values, qfmt.scales)))
    x = jax.random.normal(jax.random.PRNGKey(9), (2, D_IN))
    assert _rel(got.apply(x, w), x @ (w * mask)) <= ORACLE_REL["int8"]


# ---------------------------------------------------------------------------
# plan: values_dtype exports quantized leaves, prices real bytes, survives
# refresh
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smoke_setup():
    from repro import configs
    from repro.models import model as M
    from repro.sparse import registry as REG
    cfg = configs.get_smoke_config("qwen3-1.7b")
    key = jax.random.PRNGKey(0)
    reg = REG.build_registry(cfg)
    params = M.init_params(cfg, key, REG.k_fan_map(cfg, reg))
    masks = REG.init_sparsity_state(cfg, key, reg)["masks"]
    return cfg, reg, params, masks


def test_plan_int8_exports_quantized_leaves_and_prices_bytes(smoke_setup):
    from repro.sparse import plan as PLAN
    from repro.sparse import registry as REG
    cfg, reg, params, masks = smoke_setup
    pf = PLAN.build_plan(cfg, reg, params, masks, batch_size=1,
                         path="condensed")
    pq = PLAN.build_plan(cfg, reg, params, masks, batch_size=1,
                         path="condensed", values_dtype="int8")
    assert pq.values_dtype == "int8"
    for s in reg:
        leaf = REG.get_path(pq.serving_tree, s.path)
        assert isinstance(leaf, F.Condensed)
        assert leaf.values.dtype == jnp.int8 and leaf.scales is not None
    assert pq.weight_bytes() < pf.weight_bytes()
    assert "values_dtype=int8" in pq.describe()


def test_plan_refresh_preserves_values_dtype(smoke_setup):
    from repro.sparse import plan as PLAN
    from repro.sparse import registry as REG
    cfg, reg, params, masks = smoke_setup
    plan = PLAN.build_plan(cfg, reg, params, masks, batch_size=1,
                          path="condensed", values_dtype="int8",
                          mask_versions={s.name: 0 for s in reg})
    # topology change on every stack: drop the last quarter of columns
    new_masks = {}
    for s in reg:
        m = REG.get_path(masks, s.path)
        cut = s.d_out - max(1, s.d_out // 4)
        REG.set_path(new_masks, s.path,
                     m & (jnp.arange(s.d_out) < cut)[None, :])
    changed = plan.refresh(params, new_masks, {s.name: 1 for s in reg})
    assert set(changed) == {s.name for s in reg}
    assert plan.values_dtype == "int8"
    for s in reg:
        leaf = REG.get_path(plan.serving_tree, s.path)
        assert leaf.values.dtype == jnp.int8 and leaf.scales is not None


def test_engine_values_dtype_resolves_and_keys(smoke_setup):
    from repro.launch.engine import ServingEngine
    cfg, reg, params, masks = smoke_setup
    eng = ServingEngine(cfg, params, masks, reg, path="condensed",
                        paged=False, values_dtype="int8")
    assert eng.values_dtype == "int8"
    plan = eng.plan_for(eng.plan_key(1))
    assert plan.values_dtype == "int8"
    # "f32" resolves to None — same plans/keys as the unspecified default
    eng_f = ServingEngine(cfg, params, masks, reg, path="condensed",
                          paged=False, values_dtype="f32")
    assert eng_f.values_dtype is None


# ---------------------------------------------------------------------------
# donated refresh paths keep quantized storage without reallocating
# ---------------------------------------------------------------------------

def test_refresh_values_requantizes_in_place(wm):
    w, mask = wm[0], wm[1]
    fmt = F.Condensed.export_from_dense(w, mask, quantize_spec="int8")
    w2 = w * 1.5
    out = fmt.refresh_values(w2, mask)
    assert out.values.dtype == jnp.int8 and out.scales is not None
    fresh = F.Condensed.export_from_dense(w2, mask, quantize_spec="int8")
    np.testing.assert_array_equal(np.asarray(out.values),
                                  np.asarray(fresh.values))
    # the donated program computes amax/qmax in a different op order than
    # the fresh export — identical to float rounding, not bitwise
    np.testing.assert_allclose(np.asarray(out.scales),
                               np.asarray(fresh.scales), rtol=1e-5)


def test_donate_refresh_requantizes_new_topology(wm):
    w, mask = wm[0], wm[1]
    fmt = F.Condensed.export_from_dense(w, mask, quantize_spec="int8")
    # same fan-in, different topology: the donated fast path applies
    mask2 = topology.random_constant_fan_in_mask(
        jax.random.PRNGKey(11), D_IN, D_OUT, K)
    out = fmt.donate_refresh(w, mask2)
    fresh = F.Condensed.export_from_dense(w, mask2, quantize_spec="int8")
    np.testing.assert_array_equal(np.asarray(out.values),
                                  np.asarray(fresh.values))
    np.testing.assert_array_equal(np.asarray(out.indices),
                                  np.asarray(fresh.indices))
    np.testing.assert_allclose(np.asarray(out.scales),
                               np.asarray(fresh.scales))
