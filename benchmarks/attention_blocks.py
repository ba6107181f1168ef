"""Flash attention block sizes on one TPU, at the train-dst cell's shapes.

Times the forward and the forward + backward (``jax.grad``) of
``models.attention.flash_attention`` for each candidate ``BlockSizes``,
and of the chunked scan it replaces, at batch 8 x 2048 tokens, 16 q heads
over 8 kv heads of 128, bf16 (``bench/configs/qwen3-1.7b-train-5l.json``).
Host clock around ``block_until_ready``, median of ``--iters`` calls after
a warm-up; one causal attention core, no projections. Also prints the
flash output's and gradients' distance from the chunked scan on the chip,
and the paths ``chunked_attention`` takes when the cell's train step
(5 layers, loss and gradient) is traced.

  python benchmarks/attention_blocks.py [--iters 20] [--out FILE.jsonl]

Prints one JSON line per candidate; ``--out`` also writes them to a file.

Needs a TPU: on any other backend it exits non-zero before timing.
"""
from __future__ import annotations

import argparse
import json
import pathlib
import statistics
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path.insert(0, str(ROOT / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.experimental.pallas.ops.tpu.splash_attention import (  # noqa: E402
    splash_attention_kernel as splash)

from repro.models import attention as A  # noqa: E402

B, T, H, HKV, D = 8, 2048, 16, 8, 128
CELL = ROOT / "bench" / "configs" / "qwen3-1.7b-train-5l.json"


def _blocks(bq, bkv, bkv_compute):
    return splash.BlockSizes(
        block_q=bq, block_kv=bkv, block_kv_compute=bkv_compute,
        block_q_dkv=bq, block_kv_dkv=bkv, block_kv_dkv_compute=bkv_compute,
        block_q_dq=bq, block_kv_dq=bkv)


# "default" is flash_block_sizes at these shapes
CANDIDATES = {
    "default": None,
    "q256_kv256": _blocks(256, 256, 256),
    "q512_kv512": _blocks(512, 512, 512),
    "q1024_kv512": _blocks(1024, 512, 512),
    "q512_kv1024_c512": _blocks(512, 1024, 512),
    "q1024_kv1024": _blocks(1024, 1024, 1024),
}


def _time(fn, args, iters):
    jax.block_until_ready(fn(*args))             # compile and warm up
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(*args))
        times.append((time.perf_counter() - t0) * 1e3)
    return statistics.median(times)


def _programs(attend):
    fwd = jax.jit(attend)
    grad = jax.jit(jax.grad(
        lambda q, k, v, c: jnp.sum(attend(q, k, v).astype(jnp.float32) * c),
        argnums=(0, 1, 2)))
    return fwd, grad


def _rel(got, want):
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.linalg.norm(got - want) / jnp.linalg.norm(want))


def _cell_train_paths():
    """Paths taken when the cell's loss and gradient are traced."""
    sys.path.insert(0, str(ROOT / "bench"))
    from harness import program as PROG
    from repro.models import model as M
    from repro.sparse import registry as REG
    model = json.loads(CELL.read_text())
    cfg = PROG.arch_config(model, dtype=model["compute_dtype"],
                           param_dtype=model["param_dtype"])
    reg = REG.build_registry(cfg)
    key = jax.random.PRNGKey(0)
    params = jax.eval_shape(
        lambda k: M.init_params(cfg, k, REG.k_fan_map(cfg, reg)), key)
    masks = jax.eval_shape(
        lambda k: REG.init_sparsity_state(cfg, k, reg)["masks"], key)
    tokens = jax.ShapeDtypeStruct((B, T), jnp.int32)
    batch = {"tokens": tokens, "targets": tokens}
    before = A.path_counts()
    jax.make_jaxpr(jax.grad(lambda p, m, b: M.loss_fn(cfg, p, m, b)[0]))(
        params, masks, batch)
    after = A.path_counts()
    return {k: after[k] - before[k] for k in after}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--iters", type=int, default=20)
    ap.add_argument("--out", help="also write the rows here (JSON lines)")
    args = ap.parse_args(argv)
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"needs a TPU, found {dev.platform}", file=sys.stderr)
        return 1
    ks = jax.random.split(jax.random.PRNGKey(0), 4)
    q = jax.random.normal(ks[0], (B, T, H, D), jnp.bfloat16)
    k = jax.random.normal(ks[1], (B, T, HKV, D), jnp.bfloat16)
    v = jax.random.normal(ks[2], (B, T, HKV, D), jnp.bfloat16)
    c = jax.random.normal(ks[3], (B, T, H, D), jnp.float32)
    head_to_kv = tuple(i // (H // HKV) for i in range(H))
    rows = []
    # the chunked scan itself: the gate's backend probe is turned off while
    # its programs are traced (on their first call, here)
    chunked = _programs(lambda q, k, v: A.chunked_attention(
        q, k, v, head_to_kv=head_to_kv))
    on_tpu, A._on_tpu = A._on_tpu, lambda: False
    want = (chunked[0](q, k, v), chunked[1](q, k, v, c))
    A._on_tpu = on_tpu
    for name, blocks in [("chunked", None), *CANDIDATES.items()]:
        row = {"name": name, "device": dev.device_kind}
        try:
            fwd, grad = chunked if name == "chunked" else _programs(
                lambda q, k, v, b=blocks: A.flash_attention(
                    q, k, v, block_sizes=b))
            row["fwd_ms"] = _time(fwd, (q, k, v), args.iters)
            row["fwd_bwd_ms"] = _time(grad, (q, k, v, c), args.iters)
            if name != "chunked":
                row["out_rel_err"] = _rel(fwd(q, k, v), want[0])
                row["grad_rel_err"] = [_rel(g, w) for g, w in
                                       zip(grad(q, k, v, c), want[1])]
        except Exception as e:  # noqa: BLE001 — a refused candidate is a row
            row["error"] = f"{type(e).__name__}: {str(e)[:300]}"
        rows.append(row)
        print(json.dumps(row), flush=True)
    rows.append({"name": "cell_train_paths", **_cell_train_paths()})
    print(json.dumps(rows[-1]), flush=True)
    if args.out:
        out = pathlib.Path(args.out)
        out.parent.mkdir(parents=True, exist_ok=True)
        out.write_text("".join(json.dumps(r) + "\n" for r in rows))
    return 0


if __name__ == "__main__":
    sys.exit(main())
