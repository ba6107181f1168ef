"""Trace reduction, operation counts, the peak table, the weights made
from the seed, and the reference's topology update and its comparison, also
over stacks of expert matrices."""
import hashlib
import json
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import core, cost, peaks, reference, train
from harness import trace as TR
from harness import weights as W
from harness.archs import qwen3

TRAIN = json.loads((core.BENCH / "configs" / "qwen3-1.7b-train-5l.json")
                   .read_text())
FULL = dict(TRAIN, num_hidden_layers=28)          # the published depth


def test_union_busy_and_idle_gaps_on_events():
    dev = [("a.1", 0, 10), ("b.2", 5, 20), ("c.3", 30, 40)]
    spans = [("bench.window", 0, 50), ("bench.step", 0, 25),
             ("bench.retire", 25, 28)]
    t = TR.from_events([dev], [[]], spans)
    assert (t.lo, t.hi) == (0, 50)
    assert TR.union(dev) == [(0, 20), (30, 40)]
    assert TR.busy_seconds(t) == pytest.approx(30e-9)
    gaps = TR.idle_gaps(t)
    # 20-30 falls in no inner span at its midpoint 25 -> "bench.retire";
    # 40-50 in none
    assert gaps == [("bench.retire", pytest.approx(10e-9)),
                    ("none", pytest.approx(10e-9))]


def test_leaves_drop_a_loop_that_holds_its_body():
    ev = [("while.3", 0, 100), ("condensed_matmul.1", 10, 40),
          ("condensed_matmul.2", 50, 90), ("fusion.9", 95, 99)]
    names = TR.time_by_name(ev, 0, 100)
    assert "while.3" not in names
    assert names["condensed_matmul.1"] == pytest.approx(30e-9)
    assert TR.matching_seconds(ev, 0, 100, lambda n: "condensed_matmul" in n) \
        == pytest.approx(70e-9)
    assert TR.kind("condensed_matmul.59") == "condensed_matmul"
    assert TR.op_name("%condensed_matmul.59 = bf16[8,2048] custom-call(%x)") \
        == "condensed_matmul.59"


def test_reduction_of_a_cpu_profiler_trace(tmp_path):
    f = jax.jit(lambda x: jnp.tanh(x @ x).sum())
    x = jnp.ones((256, 256))
    f(x).block_until_ready()
    jax.profiler.start_trace(str(tmp_path))
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    # on the CPU the executor's thread plays the device
    t = TR.load(str(tmp_path), device_plane="/host:CPU",
                ops_line="tf_XLAPjRtCpuClient", modules_line="-")
    steps = [s for s in t.spans if s[0] == "bench.step"]
    assert len(steps) == 3 and t.window_s > 0
    assert t.devices and t.devices[0]
    busy = TR.busy_seconds(t)
    assert 0 < busy <= t.window_s
    b = TR.breakdown(t)
    assert b["device_ops"] and len(b["device_ops"]) <= 10
    assert sum(g for _, g in b["idle_gaps"]) <= t.window_s - busy + 1e-9


def test_counts_at_qwen3_sizes():
    assert cost.sparse_nnz(FULL) == pytest.approx(117.4e6, rel=1e-3)
    assert cost.sparse_nnz(TRAIN) == pytest.approx(117.4e6 * 5 / 28, rel=1e-3)
    # per token: 2 x nnz + dense q/k/v + head; backward twice the forward;
    # attention at every position of a causal row
    lin = (2 * cost.sparse_nnz(TRAIN) + 2 * 5 * 2048 * (2048 + 1024 + 1024)
           + 2 * 2048 * 151936)
    att = 4 * 5 * 16 * 128 * 2048 * 2049 / 2
    assert qwen3.flops_per_step(TRAIN, 1, 2048, {}) == pytest.approx(
        3 * (2048 * lin + att))


def test_unknown_device_is_an_error():
    assert peaks.peaks_for("TPU v5 lite").bf16_flops == 197e12
    with pytest.raises(KeyError):
        peaks.peaks_for("cpu")


def _program_update(w, g, mask, drop, k0, gamma):
    from repro.core import srigl as S
    spec = S.SRigLSpec("w", w.shape[0], w.shape[1], k0 / w.shape[0],
                       gamma_sal=gamma)
    st, _ = S.srigl_update(spec, w, g, S.LayerState(
        mask, jnp.ones(w.shape[1], bool)), jnp.float32(drop))
    return st.mask


@pytest.mark.parametrize("shape,k0", [((64, 48), 8), ((96, 32), 24)])
def test_reference_update_keeps_fan_in_and_follows_the_paper(shape, k0):
    """Constant fan-in after the update; the pruned are the smallest active
    magnitudes; each neuron regrows the largest gradients among its
    inactive connections; and a second witness, the program's own update,
    makes the same masks up to its threshold's resolution (it keeps one
    more survivor here, which takes one regrow's place)."""
    d_in, d_out = shape
    kw, kg, km = jax.random.split(jax.random.PRNGKey(0), 3)
    w = jax.random.normal(kw, shape)
    g = jax.random.normal(kg, shape)
    scores = jax.random.uniform(km, (d_out, d_in))
    idx = jax.lax.top_k(scores, k0)[1]
    mask = jnp.zeros((d_out, d_in), bool).at[
        jnp.arange(d_out)[:, None], idx].set(True).T
    drop = reference.drop_fraction(TRAIN["sparsity"], 100)
    n_prune = int(drop * k0 * d_out)
    new, keep = reference.srigl_layer(w, g, mask, jnp.ones(d_out, bool),
                                      n_prune, k0, 0.3, True)
    assert bool(keep.all())
    assert (jnp.sum(new, axis=0) == k0).all()
    dropped = mask & ~new
    assert int(dropped.sum()) == n_prune
    kept_min = jnp.min(jnp.where(mask & new, jnp.abs(w), jnp.inf))
    assert float(jnp.max(jnp.where(dropped, jnp.abs(w), 0))) <= kept_min
    grown = new & ~mask
    for j in range(d_out):
        cand = jnp.where(mask[:, j], -1.0, jnp.abs(g[:, j]))
        n = int(grown[:, j].sum())
        top = set(jnp.argsort(-cand)[:n].tolist())
        assert top == set(jnp.nonzero(grown[:, j])[0].tolist())
    assert int((new ^ _program_update(w, g, mask, drop, k0, 0.3)).sum()) <= 2


def test_drop_fraction_follows_the_cosine_schedule():
    sp = TRAIN["sparsity"]
    assert reference.drop_fraction(sp, 0) == pytest.approx(0.3)
    assert reference.drop_fraction(sp, 37_500) == pytest.approx(0.15)
    assert reference.drop_fraction(sp, 75_000) == 0.0


# -- weights from the seed ----------------------------------------------------


def _digest(tree) -> str:
    h = hashlib.sha256()
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    for path, leaf in sorted(flat, key=lambda kv: jax.tree_util.keystr(kv[0])):
        a = np.asarray(leaf)
        h.update(f"{jax.tree_util.keystr(path)} {a.dtype} {a.shape}".encode())
        h.update(a.tobytes())
    return h.hexdigest()


def test_qwen3_weights_and_masks_stay_what_the_seed_gave():
    """Weights and masks at the rehearsal size for one seed, bit for bit as
    the benchmark's first layout made them (its digest, taken then)."""
    _, model, _ = core.resolve(core.load_spec(), "train-dst", rehearse=True)
    params, masks = W.make(model, model["param_dtype"], 2**31 + 5)
    assert _digest((params, masks)) == (
        "1efbc3b8e1bb4b8b6a4e116f5b1ef3a38bd78364e1ce64f01b5a455185536407")


# -- stacks of expert matrices: leading dims (L, E) --------------------------

LEAD, D_IN, D_OUT, K = (2, 3), 16, 12, 4


@pytest.fixture
def experts(monkeypatch):
    """A configuration whose architecture has one sparse stack of expert
    matrices, (2 layers, 3 experts, 16, 12) at fan-in 4, and its weights,
    masks and a gradient."""
    arch = types.ModuleType("harness.archs.experts_only")
    arch.layout = lambda model: {
        ("blocks", "experts", "w_up"): W.Leaf(LEAD, (D_IN, D_OUT), "sparse",
                                              K)}
    monkeypatch.setitem(sys.modules, arch.__name__, arch)
    model = {"model_type": "experts_only",
             "sparsity": dict(TRAIN["sparsity"], fan_in={})}
    params, masks = W.make(model, "float32", 3)
    grads = jax.tree.map(
        lambda p: jax.random.normal(jax.random.PRNGKey(4), p.shape), params)
    return model, params, masks, grads


def _stack(tree):
    return tree["blocks"]["experts"]["w_up"]


def test_masks_over_expert_dims_have_constant_fan_in(experts):
    model, params, masks, _ = experts
    m = _stack(masks)
    assert m.shape == (*LEAD, D_IN, D_OUT) and m.dtype == jnp.bool_
    assert (jnp.sum(m, axis=-2) == K).all()
    # each matrix drew its own mask
    assert int((m[0, 0] ^ m[1, 2]).sum()) > 0
    assert train.fan_in_faults(model, masks, jax.tree.map(
        lambda a: jnp.ones(a.shape[:-2] + a.shape[-1:], bool), masks)) == 0


def test_dst_masks_update_each_expert_matrix_alone(experts):
    model, params, masks, grads = experts
    step = 100
    new = _stack(reference.dst_masks(model, params, grads, masks, step))
    w, g, m = _stack(params), _stack(grads), _stack(masks)
    drop = reference.drop_fraction(model["sparsity"], step)
    sp = model["sparsity"]
    for i in range(LEAD[0]):
        for e in range(LEAD[1]):
            want, _ = reference.srigl_layer(
                w[i, e], g[i, e], m[i, e], jnp.ones(D_OUT, bool),
                int(drop * int(m[i, e].sum())), K, float(sp["gamma_sal"]),
                bool(sp["ablation"]))
            assert (new[i, e] == want).all(), (i, e)
    assert not (new == m).all()


def test_update_gaps_catch_one_flip_in_one_expert(experts):
    """One connection dropped in one expert of one layer: that matrix's
    count of drops is off by one, over its own count, not the stack's."""
    model, params, masks, grads = experts
    ref = reference.dst_masks(model, params, grads, masks, 100)
    assert train.update_gaps(masks, ref, ref) == (0.0, 0.0)
    r = _stack(ref)
    kept = jnp.argwhere(r[1, 2] & _stack(masks)[1, 2])[0]
    flipped = r.at[1, 2, kept[0], kept[1]].set(False)
    new = {"blocks": {"experts": {"w_up": flipped}}}
    dropped = int((_stack(masks)[1, 2] & ~r[1, 2]).sum())
    flip, mismatch = train.update_gaps(masks, new, ref)
    assert flip == pytest.approx(1 / dropped)
    assert mismatch == pytest.approx(1 / (2 * dropped))


def test_fan_in_faults_are_counted_per_matrix(experts):
    model, _, masks, _ = experts
    m = _stack(masks)
    active = jnp.ones((*LEAD, D_OUT), bool)
    tree = lambda x: {"blocks": {"experts": {"w_up": x}}}
    # one more input to a neuron of two matrices
    broken = m.at[0, 1, :, 0].set(True).at[1, 0, :, 5].set(True)
    assert train.fan_in_faults(model, tree(broken), tree(active)) == 2
    # an ablated neuron that keeps its inputs, in a third matrix
    off = active.at[1, 1, 3].set(False)
    assert train.fan_in_faults(model, tree(broken), tree(off)) == 3
    # ablated with no inputs left: sound
    cleared = m.at[1, 1, :, 3].set(False)
    assert train.fan_in_faults(model, tree(cleared), tree(off)) == 0
