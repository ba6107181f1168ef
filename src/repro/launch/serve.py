"""Serving CLI: a thin wrapper over ``repro.launch.engine.ServingEngine``.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
      --batch 4 --prompt-len 32 --gen 16 --path condensed

Demonstrates the production serving paths (paper Sec. 4.4 — same trained
weights, multiple execution representations). Representation selection lives
in repro.sparse.plan over the typed formats of repro.sparse.formats; request
admission/grouping/execution live in repro.launch.engine. This module only
parses flags, builds the engine, submits ONE request and prints the result:

  --path auto        per-stack cost model over the request's batch BUCKET
                     (shared with the autotune cache keys): condensed gather
                     wins the bandwidth-bound decode shapes (B=1),
                     masked-dense wins the MXU back at large batch (B=256),
                     matching the paper's Sec. 4.4 crossover; ablation-ONLY
                     stacks additionally admit the column-gathered
                     structured kernel, which wins their decode shapes
  --path masked      masked-dense MXU path (bool masks; training layout)
  --path condensed   constant fan-in condensed path: sparse linears run the
                     Pallas gather kernel over Condensed formats, touching
                     only n_out*k weight entries (Alg. 1; bandwidth-bound
                     decode is where the paper's 3.4x/1.7x CPU/GPU wins live)
  --path structured  ablated neurons dropped, surviving columns gathered
                     through the structured Pallas kernel — weight bytes and
                     MXU FLOPs scale with the active fraction (Fig. 4
                     "structured" ablation — NOT output-equivalent unless the
                     sparsity is ablation-only)
  --path condensed_over_active
                     the paper's combined Fig. 4 point: ablated neurons are
                     dropped, THEN the condensed gather runs over the
                     surviving rows only. Token-identical to masked for any
                     mask (ablated outputs are exact zeros either way).

Greedy decode for masked / condensed / condensed_over_active / auto is
token-identical: all evaluate the same masked weights, only the
storage/compute representation differs.

Execution is the engine's continuous-batching scheduler where the arch
supports it: dispatches are padded to the batch bucket, KV state lives in a
paged pool (block tables over shared pages), and decode runs in chunked
jitted ``lax.scan`` programs with the pool donated — so one request here
compiles the exact programs a full request mix would reuse. ``--no-paged``
(or an arch outside ``model.supports_paged``) falls back to the legacy
exact-shape slab path: one ``lax.scan`` over the whole generation against a
contiguous donated cache.

Calibration knobs (this machine, not a spec sheet):

  --profile measured  price the --path auto cost model with rates micro-
                      benchmarked on the live backend (HardwareProfile
                      .measure(); two-point gather calibration; cached per
                      backend in the autotune cache)
  --autotune          run the timed (block_b, block_n) search for every
                      condensed stack shape at this batch bucket; winners
                      persist in the autotune cache
                      ($REPRO_AUTOTUNE_CACHE or ~/.cache/repro/autotune.json)
                      under the formats' tuning keys and are picked up by
                      the Pallas kernel wrappers at trace time
"""
from __future__ import annotations

import argparse

import jax

from repro import configs
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_serving_mesh
from repro.launch.engine import (  # noqa: F401  (re-exported API surface)
    ServingEngine, _decode_loop, _prefill, generate, serve_once)
from repro.models import model as M
from repro.sparse import plan as PLAN
from repro.sparse import registry as REG

PATHS = PLAN.PATHS


def build_plan(cfg, registry, params, masks, path: str, *,
               batch_size: int = 1, mask_versions=None,
               profile: PLAN.HardwareProfile = PLAN.DEFAULT_PROFILE,
               values_dtype: str | None = None) -> PLAN.Plan:
    """Per-stack execution plan for ``path`` at the request batch shape."""
    return PLAN.build_plan(cfg, registry, params, masks, path=path,
                           batch_size=batch_size, mask_versions=mask_versions,
                           profile=profile, values_dtype=values_dtype)


def build_serving_masks(cfg, registry, params, masks, path: str,
                        batch_size: int = 1):
    """Convert the trained (params, masks) pair into the serving pytree for
    ``path`` (leaves are repro.sparse.formats objects). Thin wrapper over
    repro.sparse.plan — the result plugs into the masks slot of
    prefill/decode_step; repro.models.layers.linear dispatches per leaf on
    its type. ``path="masked"`` returns ``masks`` unchanged (identity, no
    export) to keep the training-layout fast path allocation-free."""
    if path == "masked":
        return masks
    return build_plan(cfg, registry, params, masks, path,
                      batch_size=batch_size).serving_tree


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.ALL_ARCHS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--path", choices=PATHS, default="masked",
                    help="serving representation for sparse linears")
    ap.add_argument("--values-dtype", choices=("f32", "bf16", "int8", "fp8"),
                    default="f32",
                    help="stored width of the exported sparse values: int8/"
                         "fp8 quantize per output neuron (symmetric absmax "
                         "scale, dequant fused into the Pallas kernels — "
                         "~1 byte/weight streamed at decode), bf16 is a "
                         "plain storage cast, f32 keeps the param dtype. "
                         "Engine-wide setting; masked stacks read the live "
                         "params and are unaffected")
    ap.add_argument("--profile", choices=("default", "measured"),
                    default="default",
                    help="cost-model hardware profile for --path auto: "
                         "'measured' microbenchmarks HBM/matmul/gather rates "
                         "on this machine (two gather batch points; cached "
                         "per backend in the autotune cache file) instead "
                         "of the built-in v5e-like constants")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel shard count: builds a (1, tp) "
                         "data x model mesh and serves sparse stacks with "
                         "shard-local condensed gathers (one all-gather per "
                         "sparse layer). Requires tp visible devices; the "
                         "--path auto cost model prices the collective and "
                         "may still keep individual stacks replicated")
    ap.add_argument("--no-paged", action="store_true",
                    help="force the legacy exact-shape slab path instead of "
                         "the paged continuous-batching scheduler")
    ap.add_argument("--speculative", action="store_true",
                    help="self-draft speculative decoding: the SAME weights "
                         "at --draft-ablation extra neuron ablation draft "
                         "--gamma tokens per round, one batched full-network "
                         "dispatch verifies them (greedy output stays "
                         "bitwise identical; Result reports the measured "
                         "acceptance rate). Needs the paged scheduler and a "
                         "format-typed path (anything but masked); with a "
                         "fixed path speculation always runs, with --path "
                         "auto the cost model may decline it")
    ap.add_argument("--draft-ablation", type=float, default=0.5,
                    help="extra neuron ablation fraction of the draft "
                         "subnetwork (0.5 = draft keeps the most salient "
                         "half of each stack's active neurons)")
    ap.add_argument("--gamma", type=int, default=3,
                    help="drafted tokens per speculative round (the verify "
                         "dispatch scores gamma+1 positions)")
    ap.add_argument("--sync-dir", default=None,
                    help="subscribe to a live trainer's sync directory "
                         "(repro.sync DirChannel): bootstrap the engine "
                         "from the publisher's snapshot instead of local "
                         "init, then drain topology/values deltas at "
                         "paged-chunk boundaries while serving. Requires a "
                         "condensed-family --path matching the publisher")
    ap.add_argument("--sync-wait", type=float, default=10.0,
                    help="seconds to wait for the publisher's bootstrap "
                         "snapshot in --sync-dir before giving up")
    ap.add_argument("--autotune", action="store_true",
                    help="run the timed kernel block-shape search for every "
                         "condensed stack shape at this batch bucket before "
                         "serving (results persist in the autotune cache "
                         "and are picked up by the Pallas kernel wrappers)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (configs.get_smoke_config if args.smoke else configs.get_config)(args.arch)
    if not cfg.causal:
        raise SystemExit(f"{args.arch} is encoder-only — no decode path")
    key = jax.random.PRNGKey(args.seed)
    reg = REG.build_registry(cfg)
    params = M.init_params(cfg, key, REG.k_fan_map(cfg, reg))
    masks = REG.init_sparsity_state(cfg, key, reg)["masks"] if reg else {}
    if args.path not in ("masked", "auto") and not reg:
        raise SystemExit(f"{args.arch} has no sparse stacks — only "
                         f"--path masked/auto")
    profile = PLAN.DEFAULT_PROFILE
    if args.profile == "measured":
        profile = PLAN.HardwareProfile.measure()
        print(f"[serve] calibrated profile {profile.name}: "
              f"hbm {profile.hbm_bytes_per_s / 1e9:.1f} GB/s, "
              f"matmul {profile.mxu_flops_per_s / 1e9:.1f} GFLOP/s, "
              f"gather {profile.gather_flops_per_s / 1e9:.1f}"
              + (f"->{profile.gather_flops_per_s_large / 1e9:.1f}"
                 if profile.gather_flops_per_s_large else "")
              + " GFLOP/s")

    if (args.values_dtype != "f32" and args.path == "masked"):
        print("[serve] note: --path masked serves the live dense params; "
              f"--values-dtype {args.values_dtype} only affects exported "
              "value-storing formats (condensed/structured paths or auto)")
    mesh = None
    if args.tp > 1:
        try:
            mesh = make_serving_mesh(args.tp)
        except ValueError as e:
            raise SystemExit(f"--tp {args.tp}: {e} (simulated meshes live in "
                             "repro.launch.dryrun --program serve_tp)")
        print(f"[serve] mesh data=1 model={args.tp}: sparse stacks shard "
              "the neuron axis where the cost model prices it a win")
    speculative = None
    if args.speculative:
        from repro.launch.speculative import SpecConfig
        # a fixed path means the operator chose the representation — run
        # speculation as asked; --path auto keeps the cost model in charge
        speculative = SpecConfig(gamma=args.gamma,
                                 draft_ablation=args.draft_ablation,
                                 force=args.path != "auto")
    subscriber = None
    if args.sync_dir is not None:
        from repro.sync import DirChannel, Subscriber, engine_from_snapshot
        subscriber = Subscriber(DirChannel(args.sync_dir).subscribe("serve"),
                                name="serve")
        print(f"[serve] syncing from {args.sync_dir}: waiting up to "
              f"{args.sync_wait:.0f}s for a bootstrap snapshot")
        if not subscriber.wait_for_bootstrap(timeout=args.sync_wait):
            raise SystemExit(f"no snapshot appeared in {args.sync_dir} "
                             f"within {args.sync_wait:.0f}s — is the "
                             "trainer publishing?")
        # the published stream fixes path/values_dtype/tp; CLI flags for
        # those describe the LOCAL engine and must agree
        meta = subscriber.meta
        if args.path != meta.get("path"):
            print(f"[serve] note: stream publishes path={meta.get('path')!r}"
                  f"; serving that (not --path {args.path})")
        engine = engine_from_snapshot(
            cfg, subscriber, registry=reg, profile=profile,
            paged=False if args.no_paged else None, mesh=mesh,
            speculative=speculative)
        print(f"[serve] bootstrapped at generation {subscriber.generation} "
              f"(path={engine.path}, values_dtype={engine.values_dtype})")
    else:
        engine = ServingEngine(cfg, params, masks, reg, path=args.path,
                               profile=profile,
                               paged=False if args.no_paged else None,
                               values_dtype=args.values_dtype, mesh=mesh,
                               speculative=speculative)

    if args.autotune and args.path == "masked":
        print("[serve] --autotune skipped: --path masked never dispatches "
              "to the condensed kernels (use a condensed-family path or "
              "auto)")
    elif args.autotune and reg:
        tuned = engine.autotune(args.batch)
        for name, res in tuned.items():
            print(f"[serve] autotuned {name}: best "
                  f"{res.block_b or 'decode'}x{res.block_n} "
                  f"({res.us:.1f} us vs default {res.default_us:.1f} us)")

    prompts = jax.random.randint(key, (args.batch, args.prompt_len), 0,
                                 cfg.vocab_size)
    rid = engine.submit(prompts, args.gen)
    if args.path == "auto" and reg:
        # describe() shows BOTH the requested batch and the planned bucket —
        # the plan is keyed on the bucket (shared with autotune cache keys),
        # so --batch 2 legitimately plans at bucket 8; say so explicitly.
        print(engine.plan_for(engine.plan_key(args.batch))
              .describe(requested_batch=args.batch))
    if args.values_dtype != "f32" and reg and args.path != "masked":
        plan = engine.plan_for(engine.plan_key(args.batch))
        serving, masked_ref = plan.weight_bytes()
        print(f"[serve] values_dtype={args.values_dtype}: serving weight "
              f"bytes {serving} ({serving / max(masked_ref, 1):.3f}x of the "
              f"masked-dense reference)")
    engine.step()
    [res] = engine.retire(rid)
    b, t = prompts.shape
    print(f"[serve:{args.path}] prefill {b}x{t} in {res.prefill_s:.3f}s | "
          f"decode {b}x{args.gen} in {res.decode_s:.3f}s "
          f"({res.tok_s:.1f} tok/s)")
    print("[serve] first stream:", res.tokens[0, -args.gen:].tolist())
    if speculative is not None:
        if res.spec is not None:
            s = res.spec
            print(f"[serve:spec] gamma={s['gamma']} draft_ablation="
                  f"{s['draft_ablation']} | acceptance "
                  f"{s['acceptance_rate']:.3f} ({s['matched']}/{s['drafted']}"
                  f" drafts) | {s['full_dispatches_per_token']:.3f} "
                  f"full-network dispatches/token | draft {s['draft_s']:.3f}s"
                  f" + verify {s['verify_s']:.3f}s")
        else:
            est = engine.spec_estimate_for(res.plan_key)
            why = (f"priced {est.spec_s_per_token * 1e6:.1f} vs plain "
                   f"{est.base_s_per_token * 1e6:.1f} us/tok at assumed "
                   f"acceptance {est.acceptance}" if est else "no estimate")
            print(f"[serve:spec] declined by --path auto pricing ({why}); "
                  f"pass a fixed path to force speculation")
    if subscriber is not None:
        c = subscriber.counters
        print(f"[serve:sync] generation {subscriber.generation} | applied "
              f"{c['applied_deltas']} delta(s) + {c['applied_snapshots']} "
              f"snapshot(s) | delta bytes {c['bytes_deltas']} vs snapshot "
              f"bytes {c['bytes_snapshots']} | stale {c['stale']} dup "
              f"{c['duplicate']} gaps {c['gaps']} resyncs {c['resyncs']}")
    return res.tokens


if __name__ == "__main__":
    main()
