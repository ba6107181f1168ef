"""Training launcher.

  PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --smoke \
      --steps 200 --batch 8 --seq 64 --ckpt-dir /tmp/ckpt

Full-size configs on the production mesh are exercised via dryrun.py in this
CPU container; on a real pod this same entry point runs them (the Trainer is
mesh-agnostic: pass --mesh to place the state with launch.sharding rules).
On a multi-host pod, initialize jax.distributed before calling main() — the
per-host data pipeline shards by process_index and the checkpoint manager
writes per-host shards (see train/checkpoint.py).
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp

from repro import configs
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.launch.compile_cache import enable_compile_cache
from repro.optim.schedules import warmup_cosine
from repro.train.trainer import Trainer


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True, choices=configs.ALL_ARCHS)
    ap.add_argument("--smoke", action="store_true", help="reduced config (CPU)")
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-3)
    ap.add_argument("--sparsity", type=float, default=None)
    ap.add_argument("--method", default=None,
                    choices=[None, "srigl", "rigl", "set", "dense"])
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=100)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    enable_compile_cache()

    cfg = (configs.get_smoke_config if args.smoke else configs.get_config)(args.arch)
    sp = cfg.sparsity
    if args.sparsity is not None:
        sp = dataclasses.replace(sp, sparsity=args.sparsity)
    if args.method is not None:
        sp = dataclasses.replace(sp, method=args.method)
    cfg = cfg.replace(sparsity=sp)

    data = SyntheticLM(
        vocab_size=max(cfg.vocab_size, 2), seq_len=args.seq, batch_size=args.batch,
        seed=args.seed, family=cfg.family, n_codebooks=cfg.n_codebooks,
        d_model=cfg.d_model)
    batches = Prefetcher(
        (jax.tree.map(jnp.asarray, b) for b in data.iterate()), depth=2)

    trainer = Trainer(
        cfg=cfg,
        lr_fn=warmup_cosine(args.lr, warmup_steps=max(args.steps // 20, 1),
                            total_steps=args.steps),
        ckpt_dir=args.ckpt_dir or None, ckpt_every=args.ckpt_every, log_every=10)
    state = trainer.init_or_restore(jax.random.PRNGKey(args.seed))
    if int(state.step) > 0:
        print(f"[train] resumed from step {int(state.step)}")
    state = trainer.fit(state, batches, args.steps)
    batches.close()
    if trainer.straggler_events:
        print(f"[train] {len(trainer.straggler_events)} straggler events flagged")
    print(f"[train] done at step {int(state.step)}")
    return state


if __name__ == "__main__":
    main()
