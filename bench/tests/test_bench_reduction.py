"""Trace reduction, operation counts, the peak table, and the reference's
topology update."""
import json

import jax
import jax.numpy as jnp
import pytest

from harness import core, cost, peaks, reference
from harness import trace as TR

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
    assert cost.train_flops_per_step(TRAIN, 1, 2048) == pytest.approx(
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
