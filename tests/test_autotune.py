"""Kernel block-shape selection + timed autotune cache (repro.sparse.autotune,
kernels.condensed_matmul block logic).

The satellite contracts made executable:

* every candidate / chosen (block_b, block_n) respects the documented VMEM
  budget formula and 8x128 alignment;
* padded shapes stay exact for non-multiple (b, n_out) under auto block
  selection (both the general and the decode-specialized path);
* the decode-specialized small-batch variant is BIT-identical to the general
  kernel (same f32 accumulation per row, batch padding/tiling independent);
* the timed search's winner is never slower than the legacy 128x128 default
  on its own measured table, persists across a cache reload, and is consumed
  by kernels.ops.condensed_linear at trace time.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import condensed_matmul as cm
from repro.kernels import ops, ref
from repro.sparse import autotune as AT


@pytest.fixture()
def tmp_cache(tmp_path, monkeypatch):
    monkeypatch.setenv("REPRO_AUTOTUNE_CACHE", str(tmp_path / "autotune.json"))
    AT.reset_cache_state()
    yield tmp_path / "autotune.json"
    AT.reset_cache_state()


# ---------------------------------------------------------------------------
# block candidates: alignment + VMEM budget
# ---------------------------------------------------------------------------

SHAPE_GRID = [
    (1, 64, 32, 8),
    (8, 3072, 768, 307),
    (130, 300, 257, 5),
    (256, 3072, 768, 307),
    (1024, 16384, 4096, 64),   # budget-constrained: 128*16384 words > cap
    (4096, 65536, 8192, 32),   # extreme d_in: only minimum blocks survive
]


@pytest.mark.parametrize("b,d_in,n_out,k", SHAPE_GRID)
def test_block_candidates_respect_budget_and_alignment(b, d_in, n_out, k):
    budget = cm.vmem_budget_bytes()
    cands = cm.block_candidates(b, d_in, n_out, k)
    assert cands
    for bb, bn in cands:
        assert bb % cm.SUBLANE == 0 and bn % cm.LANE == 0
        if (bb, bn) != (cm.SUBLANE, cm.LANE):  # minimum kept unconditionally
            assert cm.fwd_vmem_words(bb, bn, d_in, k) * 4 <= budget
    for bb, bn in cm.dw_block_candidates(b, d_in, n_out, k):
        assert bb % cm.SUBLANE == 0 and bn % cm.LANE == 0
        if (bb, bn) != (cm.SUBLANE, cm.LANE):
            assert cm.dw_vmem_words(bb, bn, d_in, k) * 4 <= budget


@pytest.mark.parametrize("b,d_in,n_out,k", SHAPE_GRID)
def test_default_blocks_are_valid_candidates(b, d_in, n_out, k):
    assert cm.default_blocks(b, d_in, n_out, k) in \
        cm.block_candidates(b, d_in, n_out, k)
    assert cm.default_dw_blocks(b, d_in, n_out, k) in \
        cm.dw_block_candidates(b, d_in, n_out, k)


def test_default_blocks_keep_legacy_shape_when_it_fits():
    """The paper-benchmark layer at training batch still gets the legacy
    128x128 default (the autotuner refines it, the default must not regress)."""
    assert cm.default_blocks(256, 3072, 768, 307) == (128, 128)


def test_block_candidates_shrink_batch_dim_first():
    """When B_blk * d_in blows the budget, the batch tile shrinks before the
    neuron tile (d_in is structurally unblocked)."""
    bb, bn = cm.default_blocks(1024, 262144, 4096, 32)
    assert bb == cm.SUBLANE
    assert (bb, bn) == (8, 128)


def test_batch_bucket_monotonic_and_covering():
    assert AT.batch_bucket(1) == 1
    assert AT.batch_bucket(2) == 8
    assert AT.batch_bucket(8) == 8
    assert AT.batch_bucket(9) == 32
    # above the table the geometric x4 progression continues: the bucket
    # must COVER the batch (a plan calibrated below the dispatch batch was
    # the slab-overflow bug), never silently clamp down
    assert AT.batch_bucket(2049) == 8192
    assert AT.batch_bucket(10**9) >= 10**9
    prev = 0
    for b in list(range(1, 3000)) + [10**6, 10**9]:
        cur = AT.batch_bucket(b)
        assert cur >= b
        assert cur >= prev
        prev = cur


# ---------------------------------------------------------------------------
# exactness under auto block selection (padding paths)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,d_in,n_out,k", [
    (1, 40, 257, 5),      # decode variant, non-multiple n_out
    (3, 64, 129, 3),      # decode variant, batch not a sublane multiple
    (8, 33, 128, 4),      # decode threshold boundary
    (9, 33, 130, 4),      # just past the threshold: general kernel
    (130, 300, 257, 5),   # general kernel, both dims non-multiple
])
def test_auto_blocks_padding_stays_exact(b, d_in, n_out, k):
    key = jax.random.PRNGKey(b * 31 + k)
    x = jax.random.normal(key, (b, d_in))
    w = jax.random.normal(jax.random.fold_in(key, 1), (n_out, k))
    idx = jax.random.randint(jax.random.fold_in(key, 2), (n_out, k), 0, d_in)
    y = cm.condensed_matmul(x, w, idx)  # block_b=None -> auto dispatch
    np.testing.assert_allclose(np.array(y),
                               np.array(ref.condensed_matmul_ref(x, w, idx)),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("b", [1, 2, 5, 8])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_variant_bit_identical_to_general_kernel(b, dtype):
    """Same f32 accumulation per output row -> the decode-specialized variant
    must match the general tiled kernel BIT for bit, not just approximately."""
    d_in, n_out, k = 96, 257, 7
    key = jax.random.PRNGKey(b)
    x = jax.random.normal(key, (b, d_in), jnp.float32).astype(dtype)
    w = jax.random.normal(jax.random.fold_in(key, 1), (n_out, k),
                          jnp.float32).astype(dtype)
    idx = jax.random.randint(jax.random.fold_in(key, 2), (n_out, k), 0, d_in)
    y_dec = cm.condensed_matmul_decode(x, w, idx, block_n=128, interpret=True)
    y_gen = cm.condensed_matmul(x, w, idx, block_b=128, block_n=128,
                                interpret=True)
    assert y_dec.dtype == y_gen.dtype
    np.testing.assert_array_equal(np.array(y_dec), np.array(y_gen))


def test_dw_batch_tiling_matches_untiled():
    """Blocked-over-batch dw accumulates tile partials in f32: equal to the
    whole-batch staging within f32 roundoff, and to the oracle."""
    b, d_in, n_out, k = 130, 48, 129, 5
    key = jax.random.PRNGKey(0)
    dy = jax.random.normal(key, (b, n_out))
    x = jax.random.normal(jax.random.fold_in(key, 1), (b, d_in))
    idx = jax.random.randint(jax.random.fold_in(key, 2), (n_out, k), 0, d_in)
    dw_tiled = cm.condensed_matmul_dw(dy, x, idx, block_b=32, block_n=128,
                                      interpret=True)
    dw_whole = cm.condensed_matmul_dw(dy, x, idx, block_b=136, block_n=128,
                                      interpret=True)
    dw_ref = ref.condensed_matmul_dw_ref(dy, x, idx)
    np.testing.assert_allclose(np.array(dw_tiled), np.array(dw_whole),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.array(dw_tiled), np.array(dw_ref),
                               rtol=1e-5, atol=1e-5)


def test_dw_auto_blocks_stay_exact_and_grads_flow():
    """Auto-picked dw blocks (block_b=None) on a non-aligned training shape,
    reached through the custom-VJP backward pass."""
    b, d_in, n_out, k = 67, 40, 33, 6
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (b, d_in))
    w = jax.random.normal(jax.random.fold_in(key, 1), (n_out, k))
    idx = jax.random.randint(jax.random.fold_in(key, 2), (n_out, k), 0, d_in)
    f = lambda x, w: jnp.sum(jnp.tanh(ops.condensed_linear(x, w, idx)))
    g = lambda x, w: jnp.sum(jnp.tanh(ref.condensed_matmul_ref(x, w, idx)))
    gx1, gw1 = jax.grad(f, (0, 1))(x, w)
    gx2, gw2 = jax.grad(g, (0, 1))(x, w)
    np.testing.assert_allclose(np.array(gx1), np.array(gx2), atol=1e-5)
    np.testing.assert_allclose(np.array(gw1), np.array(gw2), atol=1e-5)


def test_interpret_default_resolves_from_backend(monkeypatch):
    assert cm.default_interpret("cpu") is True
    assert cm.default_interpret("tpu") is False
    # the retired override must not force the interpreter onto a TPU
    monkeypatch.setenv("REPRO_PALLAS_INTERPRET", "1")
    assert cm.default_interpret("tpu") is False


def test_vmem_budget_unknown_backend_is_an_error(monkeypatch):
    monkeypatch.delenv("REPRO_VMEM_CAP_BYTES", raising=False)
    assert cm.vmem_budget_bytes("tpu") == cm.vmem_budget_bytes("cpu")
    with pytest.raises(ValueError, match="no VMEM size known"):
        cm.vmem_budget_bytes("gpu")


# ---------------------------------------------------------------------------
# timed search + persistent cache
# ---------------------------------------------------------------------------

TUNE_SHAPE = dict(batch=1, d_in=48, n_out=96, k=4)


def test_autotune_winner_beats_default_on_its_table(tmp_cache):
    res = AT.autotune_blocks(reps=2, **TUNE_SHAPE)
    assert "128x128" in res.table            # legacy default always measured
    assert res.us == min(res.table.values())
    assert res.us <= res.default_us          # winner is argmin of the table
    assert res.speedup_vs_default >= 1.0
    if res.block_b is not None:              # winner respects the contracts
        assert (res.block_b, res.block_n) in cm.block_candidates(
            AT.batch_bucket(TUNE_SHAPE["batch"]), TUNE_SHAPE["d_in"],
            TUNE_SHAPE["n_out"], TUNE_SHAPE["k"]) + [(128, 128)]
    else:
        assert res.block_n % cm.LANE == 0


def test_autotune_cache_survives_reload(tmp_cache):
    res = AT.autotune_blocks(reps=2, **TUNE_SHAPE)
    AT.reset_cache_state()                   # force a re-read from disk
    got = AT.lookup_blocks(TUNE_SHAPE["batch"], TUNE_SHAPE["d_in"],
                           TUNE_SHAPE["n_out"], TUNE_SHAPE["k"])
    assert got == {"block_b": res.block_b, "block_n": res.block_n}
    assert tmp_cache.exists()
    # same bucket, different batch -> same entry; other bucket -> miss
    assert AT.lookup_blocks(1, **{k: v for k, v in TUNE_SHAPE.items()
                                  if k != "batch"}) == got
    assert AT.lookup_blocks(256, TUNE_SHAPE["d_in"], TUNE_SHAPE["n_out"],
                            TUNE_SHAPE["k"]) is None


def test_ops_consume_tuned_blocks(tmp_cache, monkeypatch):
    """condensed_linear resolves its block shape from the autotune cache at
    trace time (the tuned winner reaches the Pallas wrapper's kwargs)."""
    res = AT.autotune_blocks(reps=2, **TUNE_SHAPE)
    seen = {}

    orig_general, orig_decode = cm.condensed_matmul, cm.condensed_matmul_decode

    def spy_general(x, v, i, **kw):
        seen.update(kw)
        return orig_general(x, v, i, **kw)

    def spy_decode(x, v, i, **kw):
        seen.update(kw, decode=True)
        return orig_decode(x, v, i, **kw)

    monkeypatch.setattr(cm, "condensed_matmul", spy_general)
    monkeypatch.setattr(cm, "condensed_matmul_decode", spy_decode)

    key = jax.random.PRNGKey(0)
    x = jax.random.normal(key, (TUNE_SHAPE["batch"], TUNE_SHAPE["d_in"]))
    v = jax.random.normal(key, (TUNE_SHAPE["n_out"], TUNE_SHAPE["k"]))
    idx = jax.random.randint(key, (TUNE_SHAPE["n_out"], TUNE_SHAPE["k"]), 0,
                             TUNE_SHAPE["d_in"])
    y = ops.condensed_linear(x, v, idx)
    np.testing.assert_allclose(
        np.array(y), np.array(ref.condensed_matmul_ref(x, v, idx)),
        rtol=1e-5, atol=1e-5)
    assert seen["block_b"] == res.block_b
    assert seen["block_n"] == res.block_n


def test_ops_fall_back_to_vmem_default_without_cache(tmp_cache, monkeypatch):
    captured = {}
    orig = cm.condensed_matmul

    def spy(x, v, i, **kw):
        captured.update(kw)
        return orig(x, v, i, **kw)

    monkeypatch.setattr(cm, "condensed_matmul", spy)
    x = jnp.ones((4, 32))
    v = jnp.ones((64, 3))
    idx = jnp.zeros((64, 3), jnp.int32)
    ops.condensed_linear(x, v, idx)
    assert captured["block_b"] is None       # cm auto-dispatch decides
    assert captured["block_n"] is None


# ---------------------------------------------------------------------------
# review regressions: forced-dim block resolution + ablated-shape tuning
# ---------------------------------------------------------------------------

def test_fit_block_b_respects_budget_at_forced_block_n():
    """A caller-forced (large) neuron tile must shrink the auto batch tile
    against the SAME VMEM budget — the 128-target default would overflow."""
    budget = cm.vmem_budget_bytes()
    for words_fn in (cm.fwd_vmem_words, cm.dw_vmem_words):
        for bn in (128, 512, 1024):
            bb = cm._fit_block_b(words_fn, bn, 512, 3072, 307)
            assert bb % cm.SUBLANE == 0
            if bb != cm.SUBLANE:   # the 8-row floor is kept unconditionally
                assert words_fn(bb, bn, 3072, 307) * 4 <= budget
    # concrete overflow case from review: bn=1024 at d_in=3072, k=307 must
    # not get the bn=128-sized default batch tile
    bb = cm._fit_block_b(cm.dw_vmem_words, 1024, 512, 3072, 307)
    assert cm.dw_vmem_words(bb, 1024, 3072, 307) * 4 <= budget
    assert bb < cm.default_dw_blocks(512, 3072, 768, 307)[0]


def test_grads_exact_with_forced_block_n_only():
    """custom-VJP backward with a forced block_n and auto block_b (the
    resolution path that re-sizes the dw batch tile)."""
    b, d_in, n_out, k = 40, 48, 129, 5
    key = jax.random.PRNGKey(7)
    x = jax.random.normal(key, (b, d_in))
    w = jax.random.normal(jax.random.fold_in(key, 1), (n_out, k))
    idx = jax.random.randint(jax.random.fold_in(key, 2), (n_out, k), 0, d_in)
    f = lambda x, w: jnp.sum(jnp.tanh(ops.condensed_linear(x, w, idx,
                                                           None, 256)))
    g = lambda x, w: jnp.sum(jnp.tanh(ref.condensed_matmul_ref(x, w, idx)))
    gx1, gw1 = jax.grad(f, (0, 1))(x, w)
    gx2, gw2 = jax.grad(g, (0, 1))(x, w)
    np.testing.assert_allclose(np.array(gx1), np.array(gx2), atol=1e-5)
    np.testing.assert_allclose(np.array(gw1), np.array(gw2), atol=1e-5)


def test_tune_registry_covers_ablated_row_count(tmp_cache):
    """Stacks with ablation are tuned at BOTH the full d_out shape and the
    surviving-row shape — the latter under the FUSED coa kernel's key (the
    condensed-over-active leaf's (a, k) arrays plus the d_out-wide scatter
    are what ops looks up); ablation-ONLY stacks additionally tune the
    structured kernel's key."""
    import types

    from repro.sparse import condensed as COND
    from repro.sparse import formats as F
    stack = types.SimpleNamespace(name="s", d_in=48, d_out=96)
    stats = {"s": COND.ExportStats(k=4, max_active=64, active_fraction=0.66)}
    out = AT.tune_registry([stack], stats, batch=1, reps=1)
    assert set(out) == {"s", "s@a64"}
    assert AT.lookup_blocks(1, 48, 96, 4) is not None    # full rows
    spec = F.spec_for_stack(stack, stats["s"], 4)
    assert AT.lookup_entry(F.CondensedOverActive.spec_tuning_key(
        spec, 1)) is not None                            # surviving rows (coa)
    # NOT ablation-only (min_fan_in < d_in): no structured entry
    assert AT.lookup_entry(F.StructuredFanIn.spec_tuning_key(spec, 1)) is None
    # ablation-ONLY stack: the structured kernel's key is tuned too
    stats3 = {"s3": COND.ExportStats(k=48, max_active=64, active_fraction=0.66,
                                     min_fan_in=48)}
    stack3 = types.SimpleNamespace(name="s3", d_in=48, d_out=96)
    out3 = AT.tune_registry([stack3], stats3, batch=1, reps=1)
    assert set(out3) == {"s3", "s3@a64", "s3@structured"}
    spec3 = F.spec_for_stack(stack3, stats3["s3"], 4)
    assert AT.lookup_entry(F.StructuredFanIn.spec_tuning_key(
        spec3, 1)) is not None
    # no ablation -> only the full shape is tuned
    stats2 = {"s2": COND.ExportStats(k=4, max_active=80, active_fraction=1.0)}
    out2 = AT.tune_registry(
        [types.SimpleNamespace(name="s2", d_in=32, d_out=80)], stats2,
        batch=1, reps=1)
    assert set(out2) == {"s2"}


def test_fit_block_n_respects_budget_at_forced_block_b():
    """Mirror of the forced-block_n case: an explicit (large) batch tile must
    shrink the auto neuron tile against the budget, not take the default."""
    budget = cm.vmem_budget_bytes()
    for words_fn in (cm.fwd_vmem_words, cm.dw_vmem_words):
        for bb in (8, 128, 256):
            bn = cm._fit_block_n(words_fn, bb, 4096, 16384, 307)
            assert bn % cm.LANE == 0
            if bn != cm.LANE:
                assert words_fn(bb, bn, 16384, 307) * 4 <= budget


def test_tune_registry_keys_by_dtype_itemsize(tmp_cache):
    """Tuning at bf16 must store w16 keys — what a bf16 serving run looks up
    (serve --autotune passes the config dtype through)."""
    import types

    from repro.sparse import condensed as COND
    stack = types.SimpleNamespace(name="s", d_in=32, d_out=64)
    stats = {"s": COND.ExportStats(k=3, max_active=64, active_fraction=1.0)}
    AT.tune_registry([stack], stats, batch=1, reps=1, dtype=jnp.bfloat16)
    assert AT.lookup_blocks(1, 32, 64, 3, itemsize=2) is not None
    assert AT.lookup_blocks(1, 32, 64, 3, itemsize=4) is None
