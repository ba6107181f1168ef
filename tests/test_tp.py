"""Tensor-parallel sparse serving (PR 8).

Covers the three tentpole layers on a single host device:

* FORMAT sharding: ``tp_shards`` exports reorganize the neuron axis into tp
  contiguous blocks with locally rebased indices, and the vmap-over-blocks
  ``apply`` is exactly the replicated math (token-identity on one device is
  the ground truth the dryrun's SPMD invariants extend to a real mesh);
* COLLECTIVE-priced plans: ``stack_costs(tp=...)`` adds ``<rep>@tpN``
  candidates priced with ``profile.ici_bytes_per_s`` — the shard-vs-
  replicate decision comes out of the cost model, and the predicted
  crossover DIRECTION (sharded wins decode, replicated wins large batch)
  is pinned here per the acceptance criterion;
* ENGINE: a mesh with a model axis flows into ``PlanKey.tp``, per-shard
  autotune keys, and plans whose leaves carry the shard count.
"""
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _numerics import assert_sums_close, assert_zeros_where

from repro import configs
from repro.core import topology
from repro.launch import engine as E
from repro.models import model as M
from repro.sparse import condensed as COND
from repro.sparse import formats as F
from repro.sparse import plan as PLAN
from repro.sparse import registry as REG

D_IN, D_OUT, K, TP = 32, 48, 5, 4


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


def _stack(d_in=2048, d_out=2048, name="mlp"):
    return types.SimpleNamespace(name=name, d_in=d_in, d_out=d_out,
                                 n_replicas=1)


# ---------------------------------------------------------------------------
# format layer: TP export == replicated math
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("cls", (F.Condensed, F.CondensedOverActive),
                         ids=lambda c: c.format_name)
@pytest.mark.parametrize("which", ("fan_in", "ablated"))
def test_tp_export_apply_matches_replicated(cls, which, wm):
    w, mask, abl, _ = wm
    m = mask if which == "fan_in" else abl
    x = jax.random.normal(jax.random.PRNGKey(2), (3, D_IN))
    ref = x @ (w * m)
    f1 = cls.export_from_dense(w, m, tp_shards=1)
    f4 = cls.export_from_dense(w, m, tp_shards=TP)
    assert f1.tp == 1 and f4.tp == TP
    np.testing.assert_allclose(np.array(f4.apply(x)), np.array(ref),
                               atol=1e-5)
    # on one device the sharded block math is the replicated leaf's math:
    # neurons with no surviving input are exact zeros in both, and each
    # other output is the same K-term sum (the replicated leaf runs the
    # Pallas kernel, the blocks a jnp gather, so the orders differ)
    y4, y1 = np.array(f4.apply(x)), np.array(f1.apply(x))
    dead = ~np.asarray(m).any(axis=0)[None, :]
    assert_zeros_where(y4, dead)
    assert_zeros_where(y1, dead)
    assert_sums_close(y4, y1, np.abs(np.array(x)) @ np.abs(np.array(w * m)), K)


def test_tp_structured_export_matches_replicated(wm):
    w, _, _, abl_only = wm
    x = jax.random.normal(jax.random.PRNGKey(3), (3, D_IN))
    ref = x @ (w * abl_only)
    f4 = F.StructuredFanIn.export_from_dense(w, abl_only, tp_shards=TP)
    assert f4.tp == TP
    np.testing.assert_allclose(np.array(f4.apply(x, w)), np.array(ref),
                               atol=1e-5)


def test_tp_quantized_export_matches_replicated_quantized(wm):
    w, mask, _, _ = wm
    x = jax.random.normal(jax.random.PRNGKey(4), (2, D_IN))
    f1 = F.Condensed.export_from_dense(w, mask, quantize_spec="int8",
                                       tp_shards=1)
    f4 = F.Condensed.export_from_dense(w, mask, quantize_spec="int8",
                                       tp_shards=TP)
    assert f4.scales is not None and f4.values.dtype == jnp.int8
    # same K-term sums over the same int8 codes, in the kernel's order vs
    # the blocks' jnp order; a code is within half a step (absmax / 254) of
    # its float weight, so |x| @ |w| * 1.01 bounds the absolute terms
    abs_sum = np.abs(np.array(x)) @ np.abs(np.array(w * mask)) * 1.01
    assert_sums_close(np.array(f4.apply(x)), np.array(f1.apply(x)), abs_sum, K)


def test_tp_indices_are_locally_rebased(wm):
    """Every stored index addresses the SHARD-local input of its block —
    that is what makes the gather collective-free under GSPMD."""
    w, mask, abl, _ = wm
    wloc = D_OUT // TP
    coa = F.CondensedOverActive.export_from_dense(w, abl, tp_shards=TP)
    # out_index entries are local slots or the LOCAL sentinel (== wloc)
    assert int(jnp.max(coa.out_index)) <= wloc
    # and rebasing them reconstructs valid GLOBAL positions (sentinel d_out)
    glob = F._rebased_global_index(coa.out_index, TP, D_OUT)
    assert int(jnp.max(glob)) <= D_OUT
    live = glob[glob < D_OUT]
    assert live.size and int(jnp.max(live)) < D_OUT


def test_tp_shards_must_divide_d_out(wm):
    w, mask, _, _ = wm
    with pytest.raises(ValueError, match="must divide"):
        F.Condensed.export_from_dense(w, mask, tp_shards=5)


def test_tp_tuning_key_uses_per_shard_shapes(wm):
    """Autotune cache keys shrink to the shard-local problem (n/tp) and
    must not collide with the replicated key for the same stack."""
    w, mask, _, _ = wm
    k1 = F.Condensed.export_from_dense(w, mask, tp_shards=1).tuning_key(8)
    k4 = F.Condensed.export_from_dense(w, mask, tp_shards=TP).tuning_key(8)
    assert k1 != k4
    assert f"n{D_OUT}" in k1 and f"n{D_OUT // TP}" in k4


# ---------------------------------------------------------------------------
# collective-priced plans (acceptance: crossover direction from the model)
# ---------------------------------------------------------------------------

REALISTIC = dict(itemsize=4,
                 stats=F.ExportStats(k=205, max_active=2048,
                                     active_fraction=1.0, min_fan_in=205))


def test_sharded_condensed_wins_decode_batch():
    dec = PLAN.select_representation(_stack(), batch_size=1, tp=TP,
                                     **REALISTIC)
    assert dec.representation == "condensed" and dec.tp == TP
    assert dec.cost_key == f"condensed@tp{TP}"
    # the priced candidates include both the sharded and replicated entries
    assert f"condensed@tp{TP}" in dec.est_s and "condensed" in dec.est_s
    assert dec.est_s[dec.cost_key] < dec.est_s["condensed"]


def test_replicated_wins_large_batch():
    dec = PLAN.select_representation(_stack(), batch_size=4096, tp=TP,
                                     **REALISTIC)
    assert dec.tp == 1  # collective + gather both lose at the MXU end


def test_crossover_exists_and_is_ordered():
    cross = PLAN.tp_crossover_batch(_stack(), tp=TP, **REALISTIC)
    assert cross is not None and 1 < cross <= 4096
    below = PLAN.select_representation(_stack(), batch_size=cross // 2,
                                       tp=TP, **REALISTIC)
    at = PLAN.select_representation(_stack(), batch_size=cross, tp=TP,
                                    **REALISTIC)
    assert below.tp == TP and at.tp == 1


def test_tiny_stack_stays_replicated():
    """For tiny stacks the per-layer all-gather outweighs the sharded
    gather's byte saving at EVERY batch — the cost model must keep them
    replicated rather than sharding reflexively."""
    stats = F.ExportStats(k=8, max_active=64, active_fraction=1.0,
                          min_fan_in=8)
    dec = PLAN.select_representation(_stack(64, 64, "tiny"), batch_size=1,
                                     itemsize=4, stats=stats, tp=TP)
    assert dec.tp == 1


def test_collective_priced_with_ici_rate():
    spec = F.spec_for_stack(_stack(), REALISTIC["stats"], 4)
    fast = PLAN.DEFAULT_PROFILE
    slow = PLAN.dataclasses.replace(fast, ici_bytes_per_s=fast.ici_bytes_per_s / 100)
    c_fast = F.Condensed.estimate_collective(spec, 1, fast, TP)
    c_slow = F.Condensed.estimate_collective(spec, 1, slow, TP)
    assert c_slow == pytest.approx(c_fast * 100, rel=1e-6)
    # a 100x slower interconnect flips the decode-batch decision
    dec = PLAN.select_representation(_stack(), batch_size=1, tp=TP,
                                     itemsize=4, stats=REALISTIC["stats"],
                                     profile=slow)
    assert dec.tp == 1


def test_indivisible_stack_never_offered_sharded():
    stats = F.ExportStats(k=16, max_active=98, active_fraction=1.0,
                          min_fan_in=16)
    costs = PLAN.stack_costs(_stack(128, 98, "odd"), batch_size=1,
                             itemsize=4, k=16, active_fraction=1.0, tp=TP)
    assert not any("@tp" in key for key in costs)
    dec = PLAN.select_representation(_stack(128, 98, "odd"), batch_size=1,
                                     itemsize=4, stats=stats, tp=TP)
    assert dec.tp == 1


# ---------------------------------------------------------------------------
# plan + refresh + engine
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def smoke():
    cfg = configs.get_smoke_config("qwen3-1.7b")
    key = jax.random.PRNGKey(0)
    reg = REG.build_registry(cfg)
    params = M.init_params(cfg, key, REG.k_fan_map(cfg, reg))
    masks = REG.init_sparsity_state(cfg, key, reg)["masks"]
    return cfg, reg, params, masks


def test_build_plan_tp_exports_sharded_leaves(smoke):
    cfg, reg, params, masks = smoke
    p4 = PLAN.build_plan(cfg, reg, params, masks, path="condensed",
                         batch_size=1, tp=TP)
    assert p4.tp == TP
    for s in reg:
        leaf = REG.get_path(p4.serving_tree, s.path)
        assert leaf.tp == TP
        # arrays keep GLOBAL shapes (shard blocks are a layout, not a split)
        assert leaf.values.shape[-2] == s.d_out


def test_recondense_tp_change_forces_fresh_export(smoke):
    cfg, reg, params, masks = smoke
    s = reg[0]
    w = REG.get_path(params, s.path)
    m = REG.get_path(masks, s.path)
    stats = COND.export_stats(reg, masks, [s])[s.name]
    old = F.Condensed.export_from_dense(w, m, stats, tp_shards=1)
    new = COND.recondense_stack_leaf(w, m, stats, old, tp=TP)
    assert new.tp == TP
    # unchanged shard layout takes the donated-refresh path and keeps tp
    again = COND.recondense_stack_leaf(w, m, stats, new, tp=TP, donate=False)
    assert again.tp == TP


def test_plan_describe_shows_requested_batch_and_bucket(smoke):
    cfg, reg, params, masks = smoke
    plan = PLAN.build_plan(cfg, reg, params, masks, path="auto",
                           batch_size=8, tp=TP)
    d = plan.describe(requested_batch=2)
    assert "batch=2 (bucket 8)" in d
    assert plan.describe(requested_batch=8).count("bucket") == 0
    assert "tp=4" in d


class _FakeMesh:
    axis_names = ("data", "model")
    shape = {"data": 1, "model": TP}


def test_engine_mesh_flows_into_plan_key_and_leaves(smoke):
    cfg, reg, params, masks = smoke
    eng = E.ServingEngine(cfg, params, masks, reg, path="condensed",
                          mesh=_FakeMesh())
    assert eng.tp == TP
    key = eng.plan_key(2)
    assert key.tp == TP and f"/tp{TP}" in key.describe()
    plan = eng.plan_for(key)
    assert plan.tp == TP
    for s in reg:
        assert REG.get_path(plan.serving_tree, s.path).tp == TP
    # no mesh -> replicated keys, distinct from the TP group's
    eng1 = E.ServingEngine(cfg, params, masks, reg, path="condensed")
    assert eng1.tp == 1 and eng1.plan_key(2) != key


def test_engine_tp_tokens_identical_to_single_device(smoke):
    """Acceptance ground truth on one device: a TP engine's greedy tokens
    are IDENTICAL to the replicated engine's (the sharded apply is the same
    math reorganized; the dryrun's HLO invariants extend exactly this
    program to a real mesh)."""
    cfg, reg, params, masks = smoke
    prompts = jax.random.randint(jax.random.PRNGKey(6), (2, 8), 0,
                                 cfg.vocab_size)
    toks = {}
    for tag, mesh in (("tp1", None), ("tp4", _FakeMesh())):
        eng = E.ServingEngine(cfg, params, masks, reg, path="condensed",
                              mesh=mesh)
        rid = eng.submit(prompts, 8)
        eng.step()
        [res] = eng.retire(rid)
        toks[tag] = np.asarray(res.tokens)
    np.testing.assert_array_equal(toks["tp1"], toks["tp4"])


def test_abstract_plan_key_and_serving_tree_carry_tp(smoke):
    cfg, reg, _, _ = smoke
    key, reps = E.abstract_plan_key(cfg, reg, 2, path="condensed", tp=TP)
    assert key.tp == TP and set(reps) == {s.name for s in reg}
    tree = PLAN.abstract_serving_tree(cfg, reg,
                                      {s.name: "condensed" for s in reg},
                                      tp=TP)
    for s in reg:
        leaf = REG.get_path(tree, s.path)
        assert leaf.tp == (TP if s.d_out % TP == 0 else 1)


def test_hlo_instruction_shapes_reads_gather_dims():
    from repro.launch import hlo_analysis as H
    f = jax.jit(lambda w, i: jnp.take_along_axis(w, i, axis=0))
    hlo = f.lower(jnp.zeros((8, 4)), jnp.zeros((2, 4), jnp.int32)).compile()
    shapes = H.instruction_shapes(hlo.as_text(), "gather")
    assert shapes and all(isinstance(s, tuple) for s in shapes)
    assert (2, 4) in shapes


# a child process: four host devices exist only if forced before JAX starts
_TP_HLO_PROGRAM = """
import json, sys
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.launch.mesh import make_serving_mesh
from repro.sparse import formats as F
d_in, d_out, k, b = (int(a) for a in sys.argv[1:])
mesh = make_serving_mesh(4)
leaf = F.Condensed.abstract((), d_in, d_out, k, jnp.float32, tp=4)
x = jax.ShapeDtypeStruct((b, d_in), jnp.float32)
rep = NamedSharding(mesh, P())
out = {}
for name, spec in (("split", P("model", None)), ("replicated", P())):
    leaf_sh = jax.tree.map(lambda _: NamedSharding(mesh, spec), leaf)
    f = jax.jit(lambda l, x: l.apply(x), in_shardings=(leaf_sh, rep),
                out_shardings=rep)
    with jax.set_mesh(mesh):
        out[name] = f.lower(leaf, x).compile().as_text()
print(json.dumps(out))
"""


def test_tp_gather_check_on_compiled_cpu_hlo(tmp_path):
    """The serve_tp SPMD check against the HLO the installed JAX compiles
    for one tensor-parallel stack on a (1, 4) CPU mesh: the block-split
    program's gather reads one (nloc, k) block per device and passes; the
    same leaf left replicated gathers all four blocks and is refused."""
    import json
    import os
    import pathlib
    import subprocess
    import sys

    from repro.launch import hlo_analysis as H
    d_in, d_out, k, b = 64, 128, 6, 8
    nloc = d_out // TP
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(pathlib.Path(__file__).resolve().parents[1]
                              / "src"))
    run = subprocess.run(
        [sys.executable, "-c", _TP_HLO_PROGRAM, *map(str, (d_in, d_out, k, b))],
        env=env, capture_output=True, text=True, timeout=300, cwd=tmp_path)
    assert run.returncode == 0, run.stderr[-2000:]
    hlo = json.loads(run.stdout.splitlines()[-1])
    split = H.instruction_shapes(hlo["split"], "gather")
    replicated = H.instruction_shapes(hlo["replicated"], "gather")
    assert (1, nloc, k, b) in split
    assert (TP, nloc, k, b) in replicated
    assert H.tp_gathers_shard_local(split, nloc, k, d_out)
    assert not H.tp_gathers_shard_local(replicated, nloc, k, d_out)
    # an unblocked gather over every neuron is refused as well
    assert not H.tp_gathers_shard_local([(d_out, k, b)], nloc, k, d_out)
