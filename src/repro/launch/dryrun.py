import os
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") +
                           " --xla_force_host_platform_device_count=512").strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

The lines above run before ANY other import (jax locks the platform and the
device count on first init): the dry-run always runs on 512 forced HOST
devices, never on an attached accelerator, so it can run beside a process
that holds the chip. For each cell this script:

  1. builds the production mesh (16x16 single pod / 2x16x16 multi-pod),
  2. builds ShapeDtypeStruct stand-ins for the train/serve step inputs
     (weights, optimizer state, DST masks, batch, KV caches — no allocation),
  3. jit-lowers with explicit in/out shardings from launch/sharding.py,
  4. compiles, prints memory_analysis() (proves it fits) and cost_analysis()
     (FLOPs/bytes for §Roofline), and
  5. parses the partitioned HLO for collective traffic (hlo_analysis).

Usage:
  python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
  python -m repro.launch.dryrun --arch all [--shapes train_4k,prefill_32k]
                                [--multi-pod] [--out results.jsonl]
"""
import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.data.pipeline import make_batch_spec
from repro.launch import hlo_analysis as HLO
from repro.launch.mesh import make_mesh, make_production_mesh
from repro.launch.sharding import ShardingRules
from repro.models import model as M
from repro.sparse import registry as REG
from repro.train.state import init_train_state
from repro.train.trainer import make_train_step


def _sds_tree(tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), tree)


def _abstract_train_state(cfg):
    return jax.eval_shape(lambda k: init_train_state(cfg, k), jax.random.PRNGKey(0))


def _abstract(fn, *args):
    return jax.eval_shape(fn, *args)


def state_shardings(rules: ShardingRules, state_sds):
    from jax.sharding import NamedSharding, PartitionSpec as P
    rep = NamedSharding(rules.mesh, P())
    return type(state_sds)(
        step=rep,
        params=rules.params(state_sds.params),
        opt_state=rules.opt_state(state_sds.opt_state, state_sds.params),
        masks=rules.masks(state_sds.masks),
        neuron_active=rules.neuron_active(state_sds.neuron_active),
        grad_accum=rules.params(state_sds.grad_accum),
        mask_versions=jax.tree.map(lambda _: rep, state_sds.mask_versions),
        rng=rep,
    )


def lower_train(cfg, shape, mesh):
    rules = ShardingRules(cfg, mesh)
    registry = REG.build_registry(cfg)
    state_sds = _abstract_train_state(cfg)
    batch_sds = make_batch_spec(cfg, shape)
    # targets/labels present for training
    st_sh = state_shardings(rules, state_sds)
    b_sh = rules.batch(batch_sds, shape=shape)
    step = make_train_step(cfg, registry, lambda s: jnp.float32(1e-3),
                           microbatches=cfg.microbatches)
    jitted = jax.jit(step, in_shardings=(st_sh, b_sh),
                     out_shardings=(st_sh, None), donate_argnums=(0,))
    with jax.set_mesh(mesh):
        return jitted.lower(state_sds, batch_sds)


def lower_dst(cfg, shape, mesh):
    """The topology-update program (runs every delta_t steps)."""
    from repro.train.trainer import make_dst_step
    rules = ShardingRules(cfg, mesh)
    registry = REG.build_registry(cfg)
    if not registry:
        return None
    state_sds = _abstract_train_state(cfg)
    batch_sds = make_batch_spec(cfg, shape)
    st_sh = state_shardings(rules, state_sds)
    b_sh = rules.batch(batch_sds, shape=shape)
    # NOTE (§Perf iteration 7): per-slab sharding constraints inside the
    # lax.map get hoisted by GSPMD into whole-stack gathers (80 GB f32 for
    # kimi's expert stacks). Letting the partitioner reshard each slab
    # transiently is 4.6x cheaper — measured 318 -> 68 GB temp.
    step = make_dst_step(cfg, registry, compute_specs=None)
    jitted = jax.jit(step, in_shardings=(st_sh, b_sh), out_shardings=st_sh,
                     donate_argnums=(0,))
    with jax.set_mesh(mesh):
        return jitted.lower(state_sds, batch_sds)


def lower_serve_planned(cfg, shape, mesh, reps: dict):
    """Decode under a per-stack representation assignment ``reps`` (stack
    name -> representation), the dry-run consumer of repro.sparse.plan:
    the serving pytree is built abstractly (ShapeDtypeStructs, no
    allocation) and the planned decode program is lowered against it."""
    from repro.sparse import plan as PLAN
    rules = ShardingRules(cfg, mesh)
    registry = REG.build_registry(cfg)
    k_fan = REG.k_fan_map(cfg, registry)
    params_sds = _abstract(lambda k: M.init_params(cfg, k, k_fan), jax.random.PRNGKey(0))
    cond_sds = PLAN.abstract_serving_tree(cfg, registry, reps)
    cache_sds = _abstract(lambda: M.init_cache(cfg, shape.global_batch, shape.seq_len))
    batch_sds = make_batch_spec(cfg, shape)

    p_sh = rules.params(params_sds)
    m_sh = rules.masks(cond_sds)
    c_sh = rules.cache(cache_sds, global_batch=shape.global_batch)
    b_sh = rules.batch(batch_sds, shape=shape)

    def serve_step(params, cond, batch, cache):
        return M.decode_step(cfg, params, cond, batch, cache)

    jitted = jax.jit(serve_step,
                     in_shardings=(p_sh, m_sh, b_sh, c_sh),
                     out_shardings=(None, c_sh), donate_argnums=(3,))
    with jax.set_mesh(mesh):
        return jitted.lower(params_sds, cond_sds, batch_sds, cache_sds)


def lower_serve_condensed(cfg, shape, mesh):
    """Decode with the condensed constant fan-in representation (the paper's
    Alg. 1 serving path): weight reads shrink to n_out*k entries."""
    registry = REG.build_registry(cfg)
    return lower_serve_planned(cfg, shape, mesh,
                               {s.name: "condensed" for s in registry})


def lower_serve_structured(cfg, shape, mesh):
    """Decode with the structured (ablation) representation: the
    column-gathered kernel over abstract ``active_index`` leaves — proves
    the gathered matmul + fused scatter epilogue lower and fit at the
    padded-d_out static bound before any mask is realized."""
    registry = REG.build_registry(cfg)
    return lower_serve_planned(cfg, shape, mesh,
                               {s.name: "structured" for s in registry})


def lower_serve_plan(cfg, shape, mesh):
    """Decode under the cost-model's per-stack choice for this shape's batch
    (the ``--path auto`` program, compiled without allocation)."""
    from repro.sparse import plan as PLAN
    registry = REG.build_registry(cfg)
    reps = PLAN.plan_for_shape(cfg, registry, batch_size=shape.global_batch)
    return lower_serve_planned(cfg, shape, mesh, reps)


def lower_serve_engine(cfg, shape, mesh):
    """Decode for one ServingEngine GROUP, lowered abstractly: the plan key
    a request of this shape's batch would group under (batch bucket x
    format signature — repro.launch.engine.abstract_plan_key, no
    allocation), and the planned decode program for that group's serving
    tree. Proves every group program the engine would dispatch compiles and
    fits before a single weight is exported."""
    from repro.launch import engine as ENG
    registry = REG.build_registry(cfg)
    key, reps = ENG.abstract_plan_key(cfg, registry, shape.global_batch)
    print(f"[dryrun] engine group {key.describe()} for batch "
          f"{shape.global_batch}")
    return lower_serve_planned(cfg, shape, mesh, reps)


def lower_serve_paged(cfg, shape, mesh):
    """The continuous-batching decode program: one step against the paged
    KV pool (block tables + per-stream lengths), lowered abstractly at this
    shape's batch with the pool sharded page-wise over the batch axes.
    Proves the scheduler's decode program compiles and the pool fits at
    production scale. The dry-run pool holds exactly batch x pages-per-
    stream pages (batch-axis divisible); the engine's extra reserved
    garbage page rounds up to the next multiple in production."""
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.models import paged as PG
    if not M.supports_paged(cfg):
        raise ValueError(
            f"{cfg.name}: architecture outside the paged serving path "
            "(windowed/ring caches, M-RoPE, audio or SSM state) — use "
            "program=serve")
    rules = ShardingRules(cfg, mesh)
    registry = REG.build_registry(cfg)
    k_fan = REG.k_fan_map(cfg, registry)
    params_sds = _abstract(lambda k: M.init_params(cfg, k, k_fan),
                           jax.random.PRNGKey(0))
    if registry:
        masks_sds = _abstract(
            lambda k: REG.init_sparsity_state(cfg, k, registry)["masks"],
            jax.random.PRNGKey(0))
    else:
        masks_sds = {}
    bsz = shape.global_batch
    bs_blk = 16
    nb = PG.pages_for(shape.seq_len + bs_blk, bs_blk)
    pool_sds = _abstract(lambda: M.init_paged_pool(cfg, bsz * nb, bs_blk))
    table_sds = jax.ShapeDtypeStruct((bsz, nb), jnp.int32)
    len_sds = jax.ShapeDtypeStruct((bsz,), jnp.int32)
    batch_sds = make_batch_spec(cfg, shape)

    p_sh = rules.params(params_sds)
    m_sh = rules.masks(masks_sds)
    c_sh = rules.cache(pool_sds, global_batch=bsz)
    b_sh = rules.batch(batch_sds, shape=shape)
    bax = rules.batch_axes(bsz)
    t_sh = NamedSharding(mesh, P(bax or None, None))
    l_sh = NamedSharding(mesh, P(bax or None))

    def serve_step(params, masks, batch, pool, table, lengths):
        return M.paged_decode_step(cfg, params, masks, batch, pool, table,
                                   lengths)

    jitted = jax.jit(serve_step,
                     in_shardings=(p_sh, m_sh, b_sh, c_sh, t_sh, l_sh),
                     out_shardings=(None, c_sh), donate_argnums=(3,))
    with jax.set_mesh(mesh):
        return jitted.lower(params_sds, masks_sds, batch_sds, pool_sds,
                            table_sds, len_sds)


def lower_zoo_engine(cfg, shape, mesh, reps: dict):
    """The exact decode program a ``ServingEngine`` group dispatches for
    this arch: paged decode where the arch supports it, legacy
    contiguous-cache decode otherwise — in both cases with the PLANNED
    abstract serving tree (format-object ShapeDtypeStruct leaves) in the
    masks slot, exactly what the engine's runners execute."""
    if not M.supports_paged(cfg):
        return lower_serve_planned(cfg, shape, mesh, reps)
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.models import paged as PG
    from repro.sparse import plan as PLAN
    rules = ShardingRules(cfg, mesh)
    registry = REG.build_registry(cfg)
    k_fan = REG.k_fan_map(cfg, registry)
    params_sds = _abstract(lambda k: M.init_params(cfg, k, k_fan),
                           jax.random.PRNGKey(0))
    cond_sds = PLAN.abstract_serving_tree(cfg, registry, reps)
    bsz = shape.global_batch
    bs_blk = 16
    nb = PG.pages_for(shape.seq_len + bs_blk, bs_blk)
    pool_sds = _abstract(lambda: M.init_paged_pool(cfg, bsz * nb, bs_blk))
    table_sds = jax.ShapeDtypeStruct((bsz, nb), jnp.int32)
    len_sds = jax.ShapeDtypeStruct((bsz,), jnp.int32)
    batch_sds = make_batch_spec(cfg, shape)
    p_sh = rules.params(params_sds)
    m_sh = rules.masks(cond_sds)
    c_sh = rules.cache(pool_sds, global_batch=bsz)
    b_sh = rules.batch(batch_sds, shape=shape)
    bax = rules.batch_axes(bsz)
    t_sh = NamedSharding(mesh, P(bax or None, None))
    l_sh = NamedSharding(mesh, P(bax or None))

    def serve_step(params, cond, batch, pool, table, lengths):
        return M.paged_decode_step(cfg, params, cond, batch, pool, table,
                                   lengths)

    jitted = jax.jit(serve_step,
                     in_shardings=(p_sh, m_sh, b_sh, c_sh, t_sh, l_sh),
                     out_shardings=(None, c_sh), donate_argnums=(3,))
    with jax.set_mesh(mesh):
        return jitted.lower(params_sds, cond_sds, batch_sds, pool_sds,
                            table_sds, len_sds)


def run_zoo_cell(arch: str, smoke: bool = False, quiet: bool = False) -> dict:
    """Config-zoo serving smoke (one arch): group a decode request under
    the engine's abstract plan key, build the abstract serving tree, and
    compile the group's decode program (paged where supported). Proves the
    ``ServingEngine`` plan machinery lowers for EVERY ``configs/`` model —
    MoE expert stacks, SSM/hybrid (legacy path), multimodal, musicgen —
    before any of them is served for real. Encoder-only archs (ViT) stop
    after key + abstract tree: there is no decode program to lower."""
    import dataclasses as DC

    from repro.launch import engine as ENG
    from repro.sparse import plan as PLAN

    cfg = configs.get_smoke_config(arch) if smoke else configs.get_config(arch)
    registry = REG.build_registry(cfg)
    shapes = configs.shapes_for(arch, cfg.family, cfg.causal)
    decode = next((s for s in shapes if s.kind == "decode"), None)
    batch = decode.global_batch if decode is not None else 8
    key, reps = ENG.abstract_plan_key(cfg, registry, batch)
    tree_sds = PLAN.abstract_serving_tree(cfg, registry, reps)
    result = {
        "arch": arch, "program": "serve_zoo", "smoke": smoke,
        "family": cfg.family, "plan_key": key.describe(), "formats": reps,
        "supports_paged": M.supports_paged(cfg),
        "abstract_leaves": len(jax.tree.leaves(tree_sds)),
        "decode_shape": decode.name if decode is not None else None,
    }
    if decode is None:
        if not quiet:
            print(f"[serve_zoo] {arch}: encoder-only — plan key "
                  f"{key.describe()}, no decode program")
        return result
    shape = decode
    if smoke:
        shape = DC.replace(shape, seq_len=min(shape.seq_len, 256),
                           global_batch=min(shape.global_batch, 8))
    mesh = make_production_mesh(multi_pod=False)
    t0 = time.time()
    compiled = lower_zoo_engine(cfg, shape, mesh, reps).compile()
    result["compile_s"] = round(time.time() - t0, 1)
    mem = compiled.memory_analysis()
    result["peak_bytes"] = (getattr(mem, "argument_size_in_bytes", 0)
                            + getattr(mem, "temp_size_in_bytes", 0))
    if not quiet:
        paged = "paged" if result["supports_paged"] else "legacy"
        print(f"[serve_zoo] {arch}: group {key.describe()} ({paged}) "
              f"compiled in {result['compile_s']}s, peak "
              f"{result['peak_bytes'] / 2**30:.2f} GB/device")
    return result


def lower_serve(cfg, shape, mesh):
    if shape.kind == "prefill":
        # larger attention chunks for long-prompt prefill: fewer unrolled
        # q-chunks keeps HLO size and compile time bounded
        cfg = cfg.replace(attn_q_chunk=4096, attn_kv_chunk=2048)
    rules = ShardingRules(cfg, mesh)
    registry = REG.build_registry(cfg)
    k_fan = REG.k_fan_map(cfg, registry)

    params_sds = _abstract(lambda k: M.init_params(cfg, k, k_fan), jax.random.PRNGKey(0))
    if registry:
        masks_sds = _abstract(
            lambda k: REG.init_sparsity_state(cfg, k, registry)["masks"],
            jax.random.PRNGKey(0))
    else:
        masks_sds = {}
    cache_sds = _abstract(lambda: M.init_cache(cfg, shape.global_batch, shape.seq_len))
    batch_sds = make_batch_spec(cfg, shape)

    p_sh = rules.params(params_sds)
    m_sh = rules.masks(masks_sds)
    c_sh = rules.cache(cache_sds, global_batch=shape.global_batch)
    b_sh = rules.batch(batch_sds, shape=shape)

    step_fn = M.prefill_step if shape.kind == "prefill" else M.decode_step

    def serve_step(params, masks, batch, cache):
        return step_fn(cfg, params, masks, batch, cache)

    jitted = jax.jit(serve_step,
                     in_shardings=(p_sh, m_sh, b_sh, c_sh),
                     out_shardings=(None, c_sh), donate_argnums=(3,))
    with jax.set_mesh(mesh):
        return jitted.lower(params_sds, masks_sds, batch_sds, cache_sds)


def tp_mesh(tp: int = 4):
    """Simulated (data=1, model=tp) mesh over the forced host devices — the
    smallest mesh that exercises the tensor-parallel serving path."""
    return make_mesh((1, int(tp)), ("data", "model"))


def run_tp_cell(arch: str, shape_name: str, tp: int = 4, quiet: bool = False,
                cfg=None, smoke: bool = False) -> dict:
    """Tensor-parallel serving cell: lower the sharded PREFILL and the paged
    DECODE abstractly on a simulated (data=1, model=tp) mesh, and assert the
    SPMD invariants from the partitioned HLO:

      1. per sparse stack (isolated apply program, condensed leaves in their
         tp-block layout): EXACTLY ONE all-gather — the output-partial
         collective the cost model prices — and no other collective;
      2. every condensed gather in that program is shard-local: one of the
         tp blocks of ``(d_out/tp, k)`` per device, never all of them nor
         the unblocked ``(d_out, k)`` (``hlo_analysis.tp_gathers_shard_local``);
      3. the full prefill + paged-decode programs compile with the sharded
         serving tree, their gathers are shard-local for every divisible
         stack, and no global-shape sparse gather survives partitioning.

    These are BLOCKING checks (AssertionError fails the cell); the recorded
    timings/byte counts are trend data only. ``smoke`` swaps in the arch's
    smoke config and a small decode shape so CI can run the cell in seconds.
    """
    import dataclasses as DC

    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P
    from repro.core import distributions as D
    from repro.models import paged as PG
    from repro.sparse import formats as F
    from repro.sparse import plan as PLAN

    cfg = cfg or (configs.get_smoke_config(arch) if smoke
                  else configs.get_config(arch))
    shape = configs.SHAPES[shape_name]
    if smoke:
        shape = DC.replace(shape, seq_len=min(shape.seq_len, 256),
                           global_batch=min(shape.global_batch, 8))
    if shape.kind != "decode":
        raise ValueError(f"serve_tp runs decode shapes; got {shape_name!r} "
                         f"({shape.kind})")
    mesh = tp_mesh(tp)
    rules = ShardingRules(cfg, mesh)
    registry = REG.build_registry(cfg)
    if not registry:
        raise ValueError(f"{cfg.name}: no sparse stacks to shard")
    dt = jnp.dtype(cfg.param_dtype)
    bsz = shape.global_batch

    # -- invariant 1+2: isolated per-stack apply programs -------------------
    per_stack = {}
    tp_stacks = [s for s in registry if s.d_out % tp == 0]
    for s in tp_stacks:
        k = D.fan_in_from_density(s.d_in, s.density)
        leaf = F.Condensed.abstract((), s.d_in, s.d_out, k, dt, tp=tp)
        tree: dict = {}
        REG.set_path(tree, s.path, leaf)
        x_sds = jax.ShapeDtypeStruct((bsz, s.d_in), dt)

        def apply_fn(tree, x, _path=s.path):
            return REG.get_path(tree, _path).apply(x)

        jitted = jax.jit(apply_fn,
                         in_shardings=(rules.masks(tree),
                                       NamedSharding(mesh, P())),
                         out_shardings=NamedSharding(mesh, P()))
        with jax.set_mesh(mesh):
            hlo = jitted.lower(tree, x_sds).compile().as_text()
        pc = HLO.analyze(hlo)
        others = {c: n for c, n in pc.count_by_type.items()
                  if n and c != "all-gather"}
        gshapes = HLO.instruction_shapes(hlo, "gather")
        nloc = s.d_out // tp
        assert pc.count_by_type["all-gather"] == 1, (
            f"{s.name}: expected exactly ONE all-gather for the sharded "
            f"apply, got {pc.count_by_type}")
        assert not others, f"{s.name}: unexpected collectives {others}"
        assert HLO.tp_gathers_shard_local(gshapes, nloc, k, s.d_out), (
            f"{s.name}: gathers {gshapes} are not shard-local "
            f"(want (1, {nloc}, {k}), forbid ({tp}, {nloc}, {k}) and "
            f"({s.d_out}, {k}))")
        per_stack[s.name] = {
            "all_gather": 1, "gathers": [list(g) for g in gshapes],
            "nloc": nloc, "k": k,
            "allgather_bytes": pc.bytes_by_type["all-gather"]}
    skipped = [s.name for s in registry if s.d_out % tp != 0]

    # -- invariant 3: full sharded prefill + paged decode -------------------
    reps = {s.name: "condensed" for s in registry}
    k_fan = REG.k_fan_map(cfg, registry)
    params_sds = _abstract(lambda key: M.init_params(cfg, key, k_fan),
                           jax.random.PRNGKey(0))
    cond_sds = PLAN.abstract_serving_tree(cfg, registry, reps, tp=tp)
    p_sh = rules.params(params_sds)
    m_sh = rules.masks(cond_sds)

    def check_full(name, hlo):
        gshapes = HLO.instruction_shapes(hlo, "gather")
        for s in tp_stacks:
            k = D.fan_in_from_density(s.d_in, s.density)
            assert HLO.tp_gathers_shard_local(gshapes, s.d_out // tp, k,
                                              s.d_out), (
                f"{name}/{s.name}: sparse gathers not shard-local in the "
                f"full program: {sorted(set(gshapes))}")
        return HLO.analyze(hlo)

    timings = {}
    # prefill at the full prompt length
    pre_shape = DC.replace(shape, kind="prefill")
    pre_batch_sds = make_batch_spec(cfg, pre_shape)
    cache_sds = _abstract(lambda: M.init_cache(cfg, bsz, shape.seq_len))
    c_sh = rules.cache(cache_sds, global_batch=bsz)
    b_sh = rules.batch(pre_batch_sds, shape=pre_shape)
    t0 = time.time()
    jitted = jax.jit(lambda p, c, b, kv: M.prefill_step(cfg, p, c, b, kv),
                     in_shardings=(p_sh, m_sh, b_sh, c_sh),
                     out_shardings=(None, c_sh), donate_argnums=(3,))
    with jax.set_mesh(mesh):
        pre_hlo = jitted.lower(params_sds, cond_sds, pre_batch_sds,
                               cache_sds).compile().as_text()
    timings["prefill_s"] = round(time.time() - t0, 1)
    pre_pc = check_full("prefill", pre_hlo)

    # paged decode step (the continuous-batching program)
    if M.supports_paged(cfg):
        bs_blk = 16
        nb = PG.pages_for(shape.seq_len + bs_blk, bs_blk)
        pool_sds = _abstract(lambda: M.init_paged_pool(cfg, bsz * nb, bs_blk))
        table_sds = jax.ShapeDtypeStruct((bsz, nb), jnp.int32)
        len_sds = jax.ShapeDtypeStruct((bsz,), jnp.int32)
        dec_batch_sds = make_batch_spec(cfg, shape)
        pc_sh = rules.cache(pool_sds, global_batch=bsz)
        db_sh = rules.batch(dec_batch_sds, shape=shape)
        bax = rules.batch_axes(bsz)
        t_sh = NamedSharding(mesh, P(bax or None, None))
        l_sh = NamedSharding(mesh, P(bax or None))
        t0 = time.time()
        jitted = jax.jit(
            lambda p, c, b, pool, tb, ln: M.paged_decode_step(
                cfg, p, c, b, pool, tb, ln),
            in_shardings=(p_sh, m_sh, db_sh, pc_sh, t_sh, l_sh),
            out_shardings=(None, pc_sh), donate_argnums=(3,))
        with jax.set_mesh(mesh):
            dec_hlo = jitted.lower(params_sds, cond_sds, dec_batch_sds,
                                   pool_sds, table_sds,
                                   len_sds).compile().as_text()
        timings["decode_s"] = round(time.time() - t0, 1)
        dec_pc = check_full("paged_decode", dec_hlo)
    else:
        dec_pc = None

    # per-shard serving bytes: each device streams 1/tp of the values+indices
    itemsize = dt.itemsize
    shard_bytes = sum(
        F.Condensed.estimate_weight_bytes(F.SparseFormat.shard_spec(
            F.FormatSpec(d_in=s.d_in, d_out=s.d_out, n_replicas=s.n_replicas,
                         itemsize=itemsize,
                         k=D.fan_in_from_density(s.d_in, s.density),
                         max_active=s.d_out, active_fraction=1.0), tp))
        for s in tp_stacks)

    result = {
        "arch": arch, "shape": shape_name, "program": "serve_tp", "tp": tp,
        "mesh": f"1x{tp}", "smoke": smoke,
        "per_stack": per_stack, "skipped_stacks": skipped,
        "per_shard_values_bytes": shard_bytes,
        "prefill_collectives": pre_pc.count_by_type,
        "decode_collectives": dec_pc.count_by_type if dec_pc else None,
        **timings,
    }
    if not quiet:
        print(f"--- {arch} x {shape_name} x serve_tp (model={tp}) ---")
        for name, row in per_stack.items():
            print(f"[serve_tp] {name:24s} all-gather x1, gathers "
                  f"{row['gathers']} (nloc={row['nloc']}, k={row['k']})")
        if skipped:
            print(f"[serve_tp] replicated (d_out % {tp} != 0): {skipped}")
        print(f"[serve_tp] per-shard condensed bytes: {shard_bytes} "
              f"({shard_bytes / 2**10:.1f} KiB/device)")
        print("[serve_tp] prefill collectives:",
              {c: n for c, n in pre_pc.count_by_type.items() if n})
        if dec_pc:
            print("[serve_tp] paged-decode collectives:",
                  {c: n for c, n in dec_pc.count_by_type.items() if n})
        print(f"[serve_tp] SPMD invariants OK for {len(per_stack)} stacks")
    return result


def run_cell(arch: str, shape_name: str, multi_pod: bool, quiet: bool = False,
             program: str = "auto", cfg=None) -> dict:
    if program == "serve_tp":
        return run_tp_cell(arch, shape_name, quiet=quiet, cfg=cfg)
    cfg = cfg or configs.get_config(arch)
    shape = configs.SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    lower_fn = {"train": lower_train, "serve": lower_serve, "dst": lower_dst,
                "serve_cond": lower_serve_condensed,
                "serve_struct": lower_serve_structured,
                "serve_plan": lower_serve_plan,
                "serve_engine": lower_serve_engine,
                "serve_paged": lower_serve_paged}[
        (("train" if shape.kind == "train" else "serve") if program == "auto"
         else program)]
    t0 = time.time()
    lowered = lower_fn(cfg, shape, mesh)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    # trip-count-aware static cost model (xla's cost_analysis counts scan
    # bodies once — see hlo_analysis module docstring); bf16_equiv corrects
    # the CPU backend's f32-upcast of bf16 dots/collectives for the TPU target
    pc = HLO.analyze(hlo, bf16_equiv=(cfg.dtype == "bfloat16"))

    flops = pc.flops
    bytes_acc = pc.hbm_bytes
    terms = HLO.roofline_terms(flops, bytes_acc, pc.total_collective_bytes, n_chips)

    result = {
        "arch": arch, "shape": shape_name, "program": program,
        "mesh": "2x16x16" if multi_pod else "16x16", "chips": n_chips,
        "kind": shape.kind,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "flops_per_device": flops, "bytes_per_device": bytes_acc,
        "xla_cost_flops": float(cost.get("flops", 0.0)),
        "collective_bytes": pc.total_collective_bytes,
        "collective_by_type": pc.bytes_by_type,
        "collective_counts": pc.count_by_type,
        "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
        "output_bytes": getattr(mem, "output_size_in_bytes", 0),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
        "peak_bytes": (getattr(mem, "argument_size_in_bytes", 0)
                       + getattr(mem, "temp_size_in_bytes", 0)),
        "roofline": terms,
        "dominant": HLO.dominant_term(terms),
    }
    if not quiet:
        print(f"--- {arch} x {shape_name} x {result['mesh']} ---")
        print("memory_analysis:", mem)
        print("flops/device={:.3e} hbm_bytes/device={:.3e} peak_mem={:.2f}GB".format(
            flops, bytes_acc, result["peak_bytes"] / 2**30))
        print("collectives:", {k: f"{v/1e6:.1f}MB" for k, v in pc.bytes_by_type.items() if v})
        print("roofline:", {k: f"{v*1e3:.2f}ms" for k, v in terms.items()},
              "dominant:", result["dominant"])
    return result


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shapes", default="")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default="")
    ap.add_argument("--dst", action="store_true", help="also compile the topology-update program for train cells")
    ap.add_argument("--program", default="auto",
                    help="program to lower (auto/train/serve/serve_cond/"
                         "serve_struct/serve_plan/serve_engine/serve_paged/"
                         "serve_tp/serve_zoo)")
    ap.add_argument("--tp", type=int, default=4,
                    help="model-axis size for --program serve_tp")
    ap.add_argument("--smoke", action="store_true",
                    help="serve_tp/serve_zoo: smoke config + tiny decode "
                         "shape (CI-sized; invariants still blocking)")
    args = ap.parse_args(argv)

    archs = list(configs.ALL_ARCHS) if args.arch == "all" else [args.arch]
    results, failures = [], []
    if args.program == "serve_zoo":
        # one cell per ARCH (the zoo picks its own decode shape); sweeps the
        # whole configs/ zoo through the engine's plan machinery
        for arch in archs:
            try:
                results.append(run_zoo_cell(arch, smoke=args.smoke))
            except Exception as e:  # noqa: BLE001 — report, continue sweep
                traceback.print_exc()
                failures.append((arch, "serve_zoo", str(e)[:200]))
        if args.out:
            with open(args.out, "w") as f:
                for r in results:
                    f.write(json.dumps(r) + "\n")
        print(f"\n{len(results)} zoo cells OK, {len(failures)} failed")
        for f in failures:
            print("FAILED:", f)
        return 1 if failures else 0
    for arch in archs:
        cfg = configs.get_config(arch)
        cells = configs.shapes_for(arch, cfg.family, cfg.causal)
        if args.shapes:
            cells = [s for s in cells if s.name in args.shapes.split(",")]
        if args.program == "serve_tp":
            cells = [s for s in cells if s.kind == "decode"]
        for shape in cells:
            meshes = [False, True] if args.both_meshes else [args.multi_pod]
            programs = ([args.program] if args.program != "auto" else
                        ["auto"] + (["dst"] if shape.kind == "train"
                                    and args.dst else []))
            for mp in meshes:
                for prog in programs:
                    try:
                        if prog == "serve_tp":
                            r = run_tp_cell(arch, shape.name, tp=args.tp,
                                            smoke=args.smoke)
                        else:
                            r = run_cell(arch, shape.name, mp, program=prog)
                        results.append(r)
                    except Exception as e:  # noqa: BLE001 — report, continue sweep
                        traceback.print_exc()
                        failures.append((arch, shape.name, mp, prog, str(e)[:200]))
                    if args.out:
                        with open(args.out, "w") as f:
                            for r in results:
                                f.write(json.dumps(r) + "\n")
    print(f"\n{len(results)} cells compiled OK, {len(failures)} failed")
    for f in failures:
        print("FAILED:", f)
    return 1 if failures else 0


if __name__ == "__main__":
    sys.exit(main())
