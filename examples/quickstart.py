"""Quickstart: train a small LM with SRigL, inspect the learned structure,
export the condensed representation, and verify serving equivalence.

  PYTHONPATH=src python examples/quickstart.py
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.core import topology
from repro.core.schedule import DSTSchedule
from repro.data.pipeline import SyntheticLM
from repro.kernels import ops
from repro.sparse import registry as REG
from repro.train.state import init_train_state
from repro.train.trainer import make_dst_step, make_train_step


def main():
    # 1. a reduced qwen3-style config at 90% sparsity, SRigL with ablation
    cfg = configs.get_smoke_config("qwen3-1.7b")
    cfg = cfg.replace(sparsity=dataclasses.replace(cfg.sparsity, delta_t=10))
    registry = REG.build_registry(cfg)
    print(f"sparse stacks: {[s.name for s in registry]}")
    print(f"ERK densities: {[f'{s.density:.3f}' for s in registry]}")

    # 2. train with periodic topology updates
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, registry, lambda s: jnp.float32(3e-3)))
    dst = jax.jit(make_dst_step(cfg, registry))
    sched = DSTSchedule(delta_t=10)
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=48, batch_size=8, seed=0)
    for i in range(60):
        batch = jax.tree.map(jnp.asarray, data.batch(i))
        state, metrics = step(state, batch)
        if bool(sched.is_update_step(i + 1)):
            state = dst(state, batch)
        if i % 10 == 0:
            print(f"step {i:3d} loss {float(metrics['loss']):.4f} "
                  f"drop_frac {float(metrics['drop_fraction']):.3f}")

    # 3. learned structure: constant fan-in + neuron ablation
    summary = REG.sparsity_summary(registry, {"masks": state.masks,
                                              "neuron_active": state.neuron_active})
    for name, row in summary.items():
        print(f"{name:20s} density={row['density']:.3f} "
              f"active_neurons={row['active_neurons']:.2%}")

    # 4. condensed export: same weights, two representations (paper Sec. 4.4)
    s0 = registry[0]
    w = np.array(REG.get_path(state.params, s0.path))[0]
    m = np.array(REG.get_path(state.masks, s0.path))[0]
    k = int(m.sum(0).max())
    vals, idx = topology.dense_to_condensed(jnp.asarray(w * m), jnp.asarray(m), k)
    x = jax.random.normal(jax.random.PRNGKey(1), (2, w.shape[0]))
    err = float(jnp.max(jnp.abs(ops.condensed_linear(x, vals, idx) - x @ (w * m))))
    print(f"condensed-vs-masked max err: {err:.2e}  (fan-in k={k}, "
          f"{vals.size}/{w.size} weights stored = {vals.size/w.size:.1%})")

    # 5. serve the trained model through the programmatic ENGINE (paper
    #    Sec. 4.4): ServingEngine.submit/step/retire admits requests, groups
    #    them by PLAN KEY — the request's batch bucket (shared with the
    #    kernel-autotune cache keys) crossed with the per-stack FORMAT the
    #    cost model picks at that bucket (repro.sparse.formats: MaskedDense /
    #    Condensed / StructuredFanIn / CondensedOverActive, the four Fig. 4
    #    points) — and decodes each group with one jitted scan program.
    #    Greedy decode is token-identical to masked-dense for every exact
    #    format the plan can choose, and fusing requests into a group slab
    #    never changes a stream's tokens (greedy argmax is batch-independent).
    #    (CLI equivalent:
    #       PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
    #           --smoke --path auto)
    from repro.launch import serve
    from repro.launch.engine import ServingEngine
    engine = ServingEngine(cfg, state.params, state.masks, registry,
                           path="auto", mask_versions=state.mask_versions)
    prompts = jax.random.randint(jax.random.PRNGKey(2), (2, 8), 0,
                                 cfg.vocab_size)
    rid_a = engine.submit(prompts, gen_len=8)            # batch-2 request
    rid_b = engine.submit(prompts[:1], gen_len=8)        # batch-1 request
    groups = engine.pending_groups()
    print(f"serve: {len(groups)} plan-key group(s): "
          f"{[k.describe() for k in groups]}")
    print(engine.plan_for(engine.plan_key(2)).describe())
    engine.step()
    [res_a] = engine.retire(rid_a)
    [res_b] = engine.retire(rid_b)
    out_masked = serve.generate(cfg, state.params, state.masks, prompts, 8)
    same = bool(jnp.all(out_masked == res_a.tokens))
    print(f"serve: engine decode tokens == masked decode tokens: {same} "
          f"(batch-1 group: {res_b.tok_s:.1f} tok/s)")
    print(f"serve: first stream: {res_a.tokens[0, 8:].tolist()}")

    # 6. incremental export: keep training, then refresh the engine — only
    #    stacks whose mask-version counter moved are re-condensed (per cached
    #    plan), so a live training job can serve without a full re-export
    #    every delta_t steps. The refresh runs as jitted device programs with
    #    the old format buffers DONATED (formats.Condensed.donate_refresh):
    #    new arrays are written into the old storage whenever shapes match,
    #    so serving weight memory never doubles during a refresh (and no
    #    weight data touches the host).
    for i in range(60, 70):
        batch = jax.tree.map(jnp.asarray, data.batch(i))
        state, _ = step(state, batch)
        if bool(sched.is_update_step(i + 1)):
            state = dst(state, batch)
    changed = engine.refresh(state.params, state.masks, state.mask_versions)
    for key, names in changed.items():
        plan = engine.plan_for(key)
        print(f"serve: refresh[{key.describe()}] re-condensed "
              f"{len(names)}/{len(registry)} stacks: {names}; values-only "
              f"regathers (topology unchanged, weights trained on): "
              f"{plan.value_refreshes}")

    # 7. calibration: replace the cost model's built-in v5e-like constants
    #    with rates measured on THIS machine (HBM stream, matmul, and the
    #    gather at TWO batch points — the activation-traffic cache cliff
    #    makes one scalar gather rate mispredict large-batch crossovers;
    #    cached per backend in the autotune cache file), and let the timed
    #    block-shape search pick the Pallas kernel tiles for every condensed
    #    dispatch shape (engine.autotune derives the cache keys from the
    #    formats' tuning_key — exactly what the kernel wrappers look up).
    #    `--path auto --profile measured` / `--autotune` on the serve CLI do
    #    the same; benchmarks/kernel_autotune.py validates that the
    #    calibrated model's predicted masked/condensed crossover batch lands
    #    in the measured bucket.
    from repro.sparse import autotune, plan as PLAN
    prof = PLAN.HardwareProfile.measure()
    print(f"calibrated {prof.name}: hbm {prof.hbm_bytes_per_s / 1e9:.1f} GB/s "
          f"matmul {prof.mxu_flops_per_s / 1e9:.1f} GFLOP/s "
          f"gather {prof.gather_flops_per_s / 1e9:.1f}->"
          f"{(prof.gather_flops_per_s_large or 0) / 1e9:.1f} GFLOP/s "
          f"(b={prof.gather_small_batch}->{prof.gather_large_batch}; "
          f"cache: {autotune.cache_path()})")
    engine_m = ServingEngine(cfg, state.params, state.masks, registry,
                             path="auto", profile=prof)
    print(engine_m.plan_for(engine_m.plan_key(2)).describe())
    tuned = engine_m.autotune(2)
    for name, res in tuned.items():
        print(f"autotuned {name} @ b=2: best "
              f"{res.block_b or 'decode'}x{res.block_n} "
              f"({res.us:.0f} us vs 128x128 default {res.default_us:.0f} us)")

    # 8. ablation-aware kernels (Fig. 4 "structured" / combined points): the
    #    structured path now executes a column-GATHERED Pallas matmul — only
    #    the surviving columns' weight bytes stream per decode step and the
    #    fused one-hot epilogue writes exact zeros for ablated neurons
    #    in-kernel (no standalone scatter dispatch; same epilogue fuses the
    #    condensed-over-active scatter). On an ablation-ONLY stack (active
    #    columns fully dense) the cost model therefore lets structured WIN
    #    auto selection outright at decode shapes, and the kernel's measured
    #    step time scales with the active fraction (interpret-mode timings
    #    on this container — rankings transfer, absolute numbers do not).
    import types

    from repro.kernels import structured_matmul as SM
    from repro.sparse import formats as F
    d_in, d_out, b = 512, 512, 8
    key8 = jax.random.PRNGKey(8)
    w8 = jax.random.normal(key8, (d_in, d_out))
    x8 = jax.random.normal(jax.random.fold_in(key8, 1), (b, d_in))
    base = None
    for frac in (1.0, 0.5, 0.25):
        a = int(d_out * frac)
        ai = jnp.sort(jax.random.permutation(
            jax.random.fold_in(key8, a), d_out)[:a]).astype(jnp.int32)
        a_pad = SM.padded_active_count(a, d_out)
        ai = jnp.pad(ai, (0, a_pad - a), constant_values=d_out)
        t = autotune._time_us(
            lambda x, w, ai: SM.structured_matmul(x, w, ai), x8, w8, ai,
            reps=3)
        base = base or t
        print(f"structured kernel active={frac:.2f}: {t:8.1f} us "
              f"({t / base:.2f}x of dense-width, interpret mode)")
    stack = types.SimpleNamespace(name="mlp@abl50", d_in=3072, d_out=1024,
                                  n_replicas=1)
    stats = F.ExportStats(k=3072, max_active=512, active_fraction=0.5,
                          min_fan_in=3072)  # ablation-only: survivors dense
    for bb in (1, 256):
        dec = PLAN.select_representation(stack, batch_size=bb, itemsize=4,
                                         stats=stats, profile=prof)
        est = {r: f"{v * 1e6:.1f}us" for r, v in dec.est_s.items()}
        print(f"auto @ b={bb} (ablation-only stack) -> {dec.representation} "
              f"{est}")

    # 9. continuous batching: the engine is a request SCHEDULER, not a slab
    #    fuser. Every dispatch is padded to the plan key's batch bucket and
    #    prompts to a power-of-two length bucket, so ONE compiled prefill and
    #    ONE compiled decode program serve every request mix in the bucket
    #    (no recompile per arriving shape). KV state lives in a PAGED pool —
    #    per-stream block tables over shared pages, page 0 reserved as the
    #    garbage page padded rows point at — and decode runs in chunked
    #    jitted scans, so requests ADMIT at chunk boundaries mid-generation
    #    and finished streams free their pages without waiting for the slab.
    #    Exact-zero masking keeps every stream's greedy tokens bitwise equal
    #    to its standalone run. (CLI: repro.launch.serve, --no-paged opts
    #    out; SLA numbers: benchmarks/serve_paths.py --smoke.)
    import time
    eng9 = ServingEngine(cfg, state.params, state.masks, registry,
                         path="masked", gen_chunk=4)
    key9 = jax.random.PRNGKey(9)
    arrivals = [(jax.random.randint(jax.random.fold_in(key9, i),
                                    (2, (4, 6, 8)[i % 3]), 0, cfg.vocab_size),
                 (8, 12)[i % 2]) for i in range(6)]
    start, lat, outstanding, steps = {}, [], set(), 0
    first = None
    while arrivals or outstanding:
        if arrivals:                 # one request per chunk boundary: it
            p, g = arrivals.pop(0)   # joins the slab mid-generation of the
            rid = eng9.submit(p, g)  # earlier ones (paged pool grows, no
            first = first or (p, g, rid)     # recompile, tokens unchanged)
            start[rid] = time.perf_counter()
            outstanding.add(rid)
        eng9.step(max_chunks=1)
        steps += 1
        for res in eng9.retire():    # early finishers free pages mid-slab
            outstanding.discard(res.id)
            lat.append((time.perf_counter() - start[res.id]) * 1e3)
            if res.id == first[2]:
                ref = serve.generate(cfg, state.params, state.masks,
                                     first[0], first[1])
                print(f"serve: first request retired after {steps} chunk(s); "
                      f"tokens == standalone masked decode: "
                      f"{bool(jnp.all(res.tokens == ref))}")
    print(f"serve: continuous batching drained {len(lat)} mixed-shape "
          f"requests in {steps} chunk steps (one bucket-8 program pair): "
          f"p50 {np.percentile(lat, 50):.1f} ms  "
          f"p99 {np.percentile(lat, 99):.1f} ms")

    # 10. quantized decode: the condensed path is HBM-bytes-bound at decode,
    #     so shrinking stored values from f32 to int8 (per-output-neuron
    #     symmetric scales, dequant fused into the Pallas kernel AFTER the
    #     k-reduction) is a direct lever on the hot path. values_dtype is an
    #     ENGINE-level choice: every plan it builds exports quantized leaves,
    #     prices the real byte width, and tunes kernels under wint8 cache
    #     keys. Below: an int8 engine against the f32 engine from the same
    #     trained state — the weight-bytes ratio is computed from the
    #     EXPORTED arrays (values+scales nbytes, the hardware-transferable
    #     quantity), and greedy token agreement is measured, not assumed.
    #     (CLI equivalent:
    #        PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b \
    #            --smoke --path condensed --values-dtype int8)
    eng_f32 = ServingEngine(cfg, state.params, state.masks, registry,
                            path="condensed", paged=False)
    eng_i8 = ServingEngine(cfg, state.params, state.masks, registry,
                           path="condensed", paged=False, values_dtype="int8")
    p10 = jax.random.randint(jax.random.PRNGKey(10), (1, 8), 0,
                             cfg.vocab_size)
    toks = {}
    for name, eng in (("f32", eng_f32), ("int8", eng_i8)):
        rid = eng.submit(p10, gen_len=16)
        eng.step()
        [res] = eng.retire(rid)
        toks[name] = np.asarray(res.tokens[:, 8:])
    vb = {"f32": 0, "int8": 0}
    for name, eng in (("f32", eng_f32), ("int8", eng_i8)):
        tree = eng.plan_for(eng.plan_key(1)).serving_tree
        for s in registry:
            leaf = REG.get_path(tree, s.path)
            vb[name] += leaf.values.nbytes
            if leaf.scales is not None:
                vb[name] += leaf.scales.nbytes
    agree = float(np.mean(toks["f32"] == toks["int8"]))
    print(f"quantized decode: int8 values stream "
          f"{vb['int8']}/{vb['f32']} bytes = {vb['int8'] / vb['f32']:.3f}x "
          f"of f32 (exported values+scales; ->(k+4)/(4k) at large fan-in); "
          f"greedy token agreement vs f32: {agree:.2%}")
    print(f"quantized decode: int8 stream: {toks['int8'][0].tolist()}")

    # 11. tensor-parallel serving: constant fan-in means the condensed
    #     neuron axis partitions EXACTLY over a 'model' mesh axis — each
    #     shard holds n/tp neuron rows with locally rebased indices, the
    #     gather is shard-local (x stays replicated), and GSPMD inserts
    #     exactly ONE all-gather per sparse layer to rebuild the output.
    #     Whether that collective is worth paying is a COST-MODEL decision,
    #     not a flag: stack_costs(tp=...) adds collective-priced
    #     "<rep>@tpN" candidates (profile.ici_bytes_per_s prices the
    #     all-gather) and --path auto picks per stack. Below: the priced
    #     decision surface in-process, then the serve_tp DRYRUN as a
    #     subprocess pinned to the CPU (it forces 512 simulated host devices
    #     via XLA_FLAGS before importing jax, which this process — already
    #     running jax on the real device set — must not do; on the CPU it
    #     never competes for the chip this process holds): it lowers sharded prefill +
    #     paged decode on a simulated 4-way model mesh and ASSERTS the SPMD
    #     invariants from the lowered HLO (per-stack isolated apply: 1
    #     all-gather, 0 stray collectives, shard-local (n/tp, k) gathers),
    #     printing per-shard condensed bytes and full-program collective
    #     counts. (CLI, real multi-device host: repro.launch.serve --tp N.)
    stack11 = types.SimpleNamespace(name="mlp@tp", d_in=2048, d_out=2048,
                                    n_replicas=1)
    stats11 = F.ExportStats(k=205, max_active=2048, active_fraction=1.0,
                            min_fan_in=205)
    for bb in (1, 512):
        dec = PLAN.select_representation(stack11, batch_size=bb, itemsize=4,
                                         stats=stats11, tp=4)
        print(f"tp auto @ b={bb}: -> {dec.cost_key} "
              f"(sharded gather vs per-layer all-gather, priced at "
              f"{PLAN.DEFAULT_PROFILE.ici_bytes_per_s / 1e9:.0f} GB/s ICI)")
    cross = PLAN.tp_crossover_batch(stack11, itemsize=4, stats=stats11, tp=4)
    print(f"tp auto: predicted shard->replicate crossover batch: {cross} "
          f"(benchmarks/serve_paths.py records this per arch, schema v6)")
    import os
    import subprocess
    import sys
    proc = subprocess.run(
        [sys.executable, "-m", "repro.launch.dryrun", "--arch", "qwen3-1.7b",
         "--shapes", "decode_32k", "--program", "serve_tp", "--tp", "4",
         "--smoke"],
        capture_output=True, text=True,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    for line in proc.stdout.splitlines():
        if "[serve_tp]" in line or "cells compiled" in line:
            print(f"dryrun| {line}")
    if proc.returncode:
        print(proc.stdout[-2000:], proc.stderr[-2000:])
        raise SystemExit("serve_tp dryrun failed")

    # 12. live train->serve sync (repro.sync): the model keeps TRAINING
    #     while replicas SERVE it. The trainer publishes versioned records
    #     to a sync directory — generation 1 is a full Snapshot (bootstrap),
    #     then one Delta per publish: stacks whose mask_versions moved ship
    #     their condensed indices+values (a "topology" record — the
    #     condensed format IS the wire format), unchanged stacks ship
    #     values-only, and the dense non-stack params ride along. Subscriber
    #     replicas tail the directory and apply each generation
    #     all-or-nothing at paged-chunk boundaries through the DONATED
    #     adoption path (no weight-memory doubling, no decode recompiles);
    #     stale/duplicate records drop, a gap triggers a full-snapshot
    #     resync via the request-file back-channel. Below: publish, then
    #     run `serve.py --sync-dir` in this same process as the subscriber
    #     (in production they are two processes sharing only a directory;
    #     here one process holds the device, and a second could not get it).
    import tempfile
    from repro.sync import DirChannel, Publisher
    sync_dir = tempfile.mkdtemp(prefix="repro-sync-")
    pub = Publisher(cfg, registry, DirChannel(sync_dir), path="condensed",
                    batch_size=2, arch="qwen3-1.7b")
    info = pub.publish(state)
    print(f"sync: gen {info['generation']} {info['kind']} "
          f"{info['bytes']} B -> {sync_dir}")
    # a few more training steps: values-only deltas between DST updates,
    # a topology delta when the schedule rewires
    for i in range(60, 75):
        batch = jax.tree.map(jnp.asarray, data.batch(i))
        state, metrics = step(state, batch)
        if bool(sched.is_update_step(i + 1)):
            state = dst(state, batch)
        info = pub.publish(state)
        if info["topology"] or i % 5 == 0:
            print(f"sync: gen {info['generation']:2d} "
                  f"{'topology ' + str(info['topology']) if info['topology'] else 'values-only'}"
                  f" ({info['bytes']} B: topo {info['topology_bytes']} + "
                  f"values {info['values_bytes']} + dense "
                  f"{info['dense_bytes']})")
    serve.main(["--arch", "qwen3-1.7b", "--smoke", "--path", "condensed",
                "--batch", "2", "--prompt-len", "8", "--gen", "8",
                "--sync-dir", sync_dir])

    # 13. self-draft speculative decoding: SRigL's neuron ablation means the
    #     served model already CONTAINS its own draft model — the SAME
    #     trained weights at a higher ablation fraction. The engine derives
    #     a per-stack draft plan from the live mask (plan.derive_draft_tree;
    #     every value buffer shared BY IDENTITY with the target plan — zero
    #     extra weight residency, asserted), runs gamma cheap draft steps,
    #     then ONE batched full-network verify over the gamma+1 positions;
    #     the agreed prefix commits, the first mismatch rewinds the paged KV
    #     (overshoot pages back to the pool). Greedy acceptance keeps the
    #     token stream BITWISE identical to plain greedy decode — the knobs
    #     trade full-network dispatches per token, never correctness.
    #     Whether the draft is worth running is PRICED, not assumed
    #     (plan.price_speculation: sentinel drafts save nothing under the
    #     current kernels, column subsets do; --path auto can decline, fixed
    #     paths force). Below: acceptance and dispatches/token measured
    #     across (gamma, draft_ablation); ablation 0.0 pins the protocol
    #     ceiling — the draft IS the target, acceptance 1.0, exactly
    #     1/(gamma+1) dispatches per token.
    from repro.launch.speculative import SpecConfig
    p13 = jax.random.randint(jax.random.PRNGKey(13), (2, 8), 0,
                             cfg.vocab_size)
    eng_ref = ServingEngine(cfg, state.params, state.masks, registry,
                            path="condensed")
    rid = eng_ref.submit(p13, gen_len=16)
    eng_ref.step()
    [ref13] = eng_ref.retire(rid)
    for gamma, frac in ((3, 0.0), (3, 0.5), (2, 0.5)):
        eng13 = ServingEngine(
            cfg, state.params, state.masks, registry, path="condensed",
            speculative=SpecConfig(gamma=gamma, draft_ablation=frac,
                                   force=True))
        rid = eng13.submit(p13, gen_len=16)
        eng13.step()
        [res13] = eng13.retire(rid)
        s = res13.spec
        print(f"spec g={gamma} abl={frac}: acceptance "
              f"{s['acceptance_rate']:.2f}, full-network dispatches/token "
              f"{s['full_dispatches_per_token']:.3f}, bitwise == plain: "
              f"{bool(jnp.all(res13.tokens == ref13.tokens))}")
    est13 = eng13.spec_estimate_for(eng13.plan_key(2))
    print(f"spec pricing @ smoke dims: draft {est13.draft_step_s * 1e6:.0f}us"
          f" vs target {est13.target_step_s * 1e6:.0f}us per step -> "
          f"auto would {'run' if est13.worthwhile else 'decline'} "
          f"(lane padding hides tiny-dim savings; realistic d_out wins)")
    # the CLI drives the same thing: --speculative --gamma G
    # --draft-ablation F (a fixed --path forces; --path auto prices)
    serve.main(["--arch", "qwen3-1.7b", "--smoke", "--path", "condensed",
                "--batch", "2", "--prompt-len", "8", "--gen", "16",
                "--speculative", "--gamma", "3", "--draft-ablation", "0.5"])


if __name__ == "__main__":
    main()
