"""Bring-up smoke of the main path on a TPU, through the user entry points.

  python chip_smoke.py                 # one chip: serve + train, qwen3-1.7b
  python chip_smoke.py --chips 4       # four chips: tensor-parallel serve only
  JAX_PLATFORMS=cpu python chip_smoke.py --rehearse [--chips 4]
                                       # the same phases at smoke size on the
                                       # CPU (Pallas interpreted); no ok line
  JAX_PLATFORMS=cpu python chip_smoke.py --aot
                                       # compile the full-width paged prefill
                                       # and decode for a described v5e and
                                       # print memory_analysis(); no ok line

One chip (default):
  * serve — ``ServingEngine`` at qwen3-1.7b's published widths (28 layers,
    d_model 2048, d_ff 6144, vocab 151,936, bf16 compute, 90% SRigL) on the
    paged scheduler, ``path="condensed"`` and ``path="masked"`` on the same
    random weights: a B=1 and a B=8 request, 128 prompt tokens, 32
    generated. Prints cold prefill/decode seconds (compile included), peak
    device memory, checks the condensed decode program holds the Mosaic
    kernels, and compares the two paths' prefill logits and greedy tokens.
  * train — ``Trainer`` with SRigL at full width, depth cut to what Adam
    fits on one chip, a few steps with one DST mask update: the loss stays
    finite and every sparse stack keeps a constant fan-in.

Four chips (``--chips 4``): ``ServingEngine(mesh=(1, 4))`` at full width
on the mesh ``serve.py --tp 4`` builds, ``path="condensed"``, B=8, against a
one-chip masked engine on device 0 of the same process: which stacks the
plan shards, that their leaves span 4 devices, that the compiled decode
program all-gathers, and the logits/tokens comparison.

The last stdout line of a passing chip run is exactly
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failed phase exits non-zero without it. Without a TPU the device check
fails first. Everything runs in this one process: a process that touched
JAX holds the chip, and a child could not get it.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import pathlib
import re
import sys
import time
import traceback

import numpy as np

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "qwen3-1.7b"
PROMPT_LEN, GEN_LEN = 128, 32
SEED = 0
# share of HBM the smoke plans to fill; the rest is headroom for XLA
# temporaries (initialisation, the condensed export, the DST update's
# sorts) that the reckoning below does not itemize
HBM_PLAN_FRACTION = 0.7
# logits tolerance: relative RMS of (path - reference) over the reference
# logits' spread. Both paths multiply bf16 activations by bf16-rounded
# weights and accumulate in f32, but in different orders (the condensed
# kernel slot by slot, the MXU in its own tree, the tensor-parallel blocks
# per shard), and every sparse linear rounds its output to bf16 (a 2^-8
# relative step). An order difference flips some of those roundings, and
# qwen3-1.7b's 112 sparse linears, with attention and norms between them,
# compound the flips to a few percent of the logits' spread. 0.1 leaves
# that headroom and still fails a wrong gather, whose logits are unrelated
# to the reference's (relative RMS ~1.4).
LOGITS_REL_RMS_TOL = 0.1


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--rehearse", action="store_true",
                    help="run the phases at smoke size on the CPU (needs "
                         "JAX_PLATFORMS=cpu); never prints the ok line")
    ap.add_argument("--aot", action="store_true",
                    help="compile full-width prefill/decode for a described "
                         "v5e from the CPU; never prints the ok line")
    return ap.parse_args(argv)


class Smoke:
    """Phase runner: every phase reports, and one failure fails the run."""

    def __init__(self):
        self.failed: list[str] = []

    def run(self, name, fn, *a, **kw):
        print(f"[{name}] start", flush=True)
        t0 = time.perf_counter()
        try:
            out = fn(*a, **kw)
        except Exception:  # noqa: BLE001 — report every phase, then fail
            traceback.print_exc()
            print(f"[{name}] FAILED after {time.perf_counter() - t0:.1f}s",
                  flush=True)
            self.failed.append(name)
            return None
        finally:
            # a phase's engines sit in reference cycles, which only the
            # cyclic collector frees: without it the serve phase's ~12 GB
            # stay on the device and the train phase runs out of HBM
            gc.collect()
        print(f"[{name}] ok in {time.perf_counter() - t0:.1f}s", flush=True)
        return out


def log(msg: str) -> None:
    print(msg, flush=True)


class SmokeCheckFailed(AssertionError):
    pass


def check(ok, msg: str) -> None:
    """A smoke check; raises (unlike ``assert``, also under ``python -O``)."""
    if not ok:
        raise SmokeCheckFailed(msg)


def cache_entries(path: str) -> int:
    """Number of compiled programs stored under ``path`` (0 if absent)."""
    p = pathlib.Path(path)
    return sum(1 for f in p.iterdir() if f.is_file()) if p.is_dir() else 0


# ---------------------------------------------------------------------------
# memory reckoning
# ---------------------------------------------------------------------------


def _stack_counts(cfg):
    from repro.core import distributions as D
    from repro.sparse import registry as REG
    reg = REG.build_registry(cfg)
    sparse = sum(s.n_replicas * s.d_in * s.d_out for s in reg)
    nnz = sum(s.n_replicas * s.d_out * D.fan_in_from_density(s.d_in, s.density)
              for s in reg)
    return reg, sparse, nnz


def _param_count(cfg) -> int:
    import jax
    from repro.models import model as M
    from repro.sparse import registry as REG
    reg = REG.build_registry(cfg)
    sds = jax.eval_shape(lambda k: M.init_params(cfg, k, REG.k_fan_map(cfg, reg)),
                         jax.random.PRNGKey(0))
    return sum(math.prod(x.shape) for x in jax.tree.leaves(sds))


def serve_storage(cfg, hbm: int, copies: int):
    """Pick the parameter storage dtype for serving: f32 (the config
    default) when ``copies`` of the params, the bool masks, the condensed
    values + int32 indices and the KV pool fit the planned share of HBM,
    else bf16. Prints the reckoning; never changes a width."""
    _, sparse, nnz = _stack_counts(cfg)
    n = _param_count(cfg)
    kv_per_tok = 2 * cfg.n_layers * cfg.n_kv_heads_padded * cfg.head_dim * 2
    kv = 16 * kv_per_tok * (PROMPT_LEN + GEN_LEN)        # generous pool bound
    budget = int(HBM_PLAN_FRACTION * hbm)
    for dtype, item in (("float32", 4), ("bfloat16", 2)):
        total = copies * (n * item + sparse) + nnz * (item + 4) + kv
        log(f"[reckon] serve {dtype}: {copies} x ({n / 1e9:.3f} B params x "
            f"{item} B + {sparse / 1e9:.3f} B mask bools) + condensed "
            f"{nnz / 1e6:.1f} M x ({item} + 4) B + KV {kv / 2**20:.0f} MiB "
            f"= {total / 1e9:.2f} GB vs {HBM_PLAN_FRACTION:.0%} of HBM "
            f"{budget / 1e9:.2f} GB")
        if total <= budget:
            break
    else:
        raise RuntimeError("even bf16 storage exceeds the HBM plan")
    if dtype != cfg.param_dtype:
        log(f"[reckon] serving with param_dtype={dtype} (f32 storage does "
            f"not fit); widths unchanged")
    return cfg.replace(param_dtype=dtype)


def train_depth(cfg, hbm: int) -> int:
    """Layers that fit with Adam: each parameter costs its f32 value, two
    f32 moments, its f32 gradient and the DST step's recomputed dense
    gradient (20 B), each sparse weight one mask byte."""
    one = cfg.replace(n_layers=1)
    n1 = _param_count(one)
    n2 = _param_count(cfg.replace(n_layers=2))
    per_layer = n2 - n1
    fixed = n1 - per_layer                               # embedding, norms
    _, sparse1, _ = _stack_counts(one)
    budget = int(HBM_PLAN_FRACTION * hbm)
    per_layer_bytes = 20 * per_layer + sparse1
    depth = max(1, min(cfg.n_layers, (budget - 20 * fixed) // per_layer_bytes))
    log(f"[reckon] train: embedding {fixed / 1e6:.1f} M params x 20 B = "
        f"{20 * fixed / 1e9:.2f} GB; per layer {per_layer / 1e6:.1f} M x 20 B "
        f"+ {sparse1 / 1e6:.1f} M mask B = {per_layer_bytes / 1e9:.2f} GB; "
        f"{HBM_PLAN_FRACTION:.0%} of HBM = {budget / 1e9:.2f} GB -> "
        f"{depth} of {cfg.n_layers} layers (width unchanged)")
    return int(depth)


# ---------------------------------------------------------------------------
# serving helpers
# ---------------------------------------------------------------------------


def _init_weights(cfg):
    """The construction serve.py uses: params, SRigL masks, registry.
    Masks first: at full width their initialisation takes ~7 GB of
    temporaries (v5e memory_analysis), which must not sit on top of the
    ~6.9 GB of f32 params."""
    import jax
    from repro.models import model as M
    from repro.sparse import registry as REG
    key = jax.random.PRNGKey(SEED)
    reg = REG.build_registry(cfg)
    masks = REG.init_sparsity_state(cfg, key, reg)["masks"]
    params = M.init_params(cfg, key, REG.k_fan_map(cfg, reg))
    return reg, params, masks


def _prompts(cfg, b: int):
    import jax
    return jax.random.randint(jax.random.PRNGKey(100 + b), (b, PROMPT_LEN), 0,
                              cfg.vocab_size)


def _runner_inputs(eng, key, tokens, lens):
    """Fresh pool/table/lengths in the shapes the group's compiled programs
    were built for (so the dispatch reuses them)."""
    import jax.numpy as jnp
    from repro.models import model as M
    runner = eng._runners[key]
    bucket = runner.bucket
    table = 1 + np.arange(bucket * runner.nb, dtype=np.int32).reshape(
        bucket, runner.nb)
    tok = np.zeros((bucket, tokens.shape[1]), np.int32)
    tok[:tokens.shape[0]] = np.asarray(tokens)
    ln = np.zeros((bucket,), np.int32)
    ln[:len(lens)] = lens
    pool = M.init_paged_pool(eng.cfg, runner.num_blocks, runner.bs)
    return jnp.asarray(tok), pool, jnp.asarray(table), jnp.asarray(ln)


def prefill_logits(eng, key, tokens, lens):
    """(B, V) f32 logits at each row's last real token, through the group's
    paged prefill program. ``tokens`` is right-padded to a power of two."""
    from repro.launch import engine as E
    tok, pool, table, ln = _runner_inputs(eng, key, tokens, lens)
    logits, _, secs, cold = E._paged_prefill_dispatch(
        eng.cfg, eng.params, eng.serving_tree_for(key), tok, pool, table, ln)
    return logits[:tokens.shape[0]], secs, cold


def decode_program_text(eng, key) -> str:
    """Optimized HLO of the group's compiled decode-chunk program."""
    import jax.numpy as jnp
    from repro.launch import engine as E
    runner = eng._runners[key]
    tok, pool, table, ln = _runner_inputs(
        eng, key, jnp.zeros((1, 1), jnp.int32), [0])
    chunk = min(eng.gen_chunk, GEN_LEN)
    return E._paged_decode_chunk.lower(
        eng.cfg, eng.params, eng.serving_tree_for(key), pool, table, ln,
        jnp.zeros((runner.bucket, 1), jnp.int32), chunk).compile().as_text()


def serve_requests(eng, prompts: dict, tag: str):
    """Submit, step, retire; returns {batch: Result}."""
    rids = {b: eng.submit(p, GEN_LEN) for b, p in prompts.items()}
    t0 = time.perf_counter()
    eng.step()
    wall = time.perf_counter() - t0
    res = {b: eng.retire(rid)[0] for b, rid in rids.items()}
    for b, r in res.items():
        log(f"[{tag}] B={b}: prefill {r.prefill_s:.3f}s, decode "
            f"{GEN_LEN} tokens {r.decode_s:.3f}s (cold: compile included, "
            f"cold={r.cold}; not a speed)")
    log(f"[{tag}] step() wall {wall:.1f}s for B={sorted(prompts)}")
    return res


def compare_logits(tag: str, got, ref, what: str = "prefill logits") -> float:
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    check(np.all(np.isfinite(got)) and np.all(np.isfinite(ref)),
          f"{tag}: non-finite logits")
    spread = ref - ref.mean(axis=-1, keepdims=True)
    rel = float(np.sqrt(np.mean((got - ref) ** 2) / np.mean(spread ** 2)))
    log(f"[{tag}] {what}: relative RMS diff {rel:.3e} "
        f"(tolerance {LOGITS_REL_RMS_TOL}), max |diff| "
        f"{float(np.max(np.abs(got - ref))):.4f}, logit std "
        f"{float(np.std(ref)):.4f}")
    check(rel <= LOGITS_REL_RMS_TOL, f"{tag}: logits differ by {rel:.3e} "
          f"relative RMS > {LOGITS_REL_RMS_TOL}")
    return rel


def compare_tokens(tag: str, res, ref, eng, ref_eng) -> None:
    """Greedy tokens equal, or each divergence is a near-tie that the
    programs' own logits account for.

    At a diverged row's first divergent step ``s``, both engines' logits
    are recomputed, teacher-forced on the shared prefix, by the program
    that chose that token: the paged prefill at step 0, else a prefill of
    the prompt and the first ``s - 1`` generated tokens followed by one
    paged decode step (the step the decode-chunk program scans) on token
    ``s - 1``. Then (a) those logits meet the logits tolerance, and (b) the
    path's token trails the reference's best by at most twice the largest
    logit difference between the paths at that step — the most such a
    difference can move the gap between two logits. (b) is a top-k test
    with k set by the noise the step shows rather than fixed: at a max
    difference of ~0.1 (bf16 storage, four chips) the reference's third
    choice can sit inside it. The token's rank by value under the
    reference is printed. A wrong decode kernel fails (a)."""
    for b in sorted(res):
        got = np.asarray(res[b].tokens)[:, PROMPT_LEN:]
        want = np.asarray(ref[b].tokens)[:, PROMPT_LEN:]
        rows = [r for r in range(b) if not np.array_equal(got[r], want[r])]
        if not rows:
            log(f"[{tag}] B={b}: greedy tokens identical ({b} x {GEN_LEN})")
            continue
        full = np.asarray(ref[b].tokens)
        steps = [int(np.argmax(got[r] != want[r])) for r in rows]
        log(f"[{tag}] B={b}: {len(rows)} of {b} rows diverge, first at "
            f"steps {steps}")
        width = 1 << (PROMPT_LEN + GEN_LEN - 1).bit_length()
        tokens = np.zeros((len(rows), width), np.int32)
        lens, cur = [], []
        for i, (r, s) in enumerate(zip(rows, steps)):
            n = PROMPT_LEN + max(s - 1, 0)
            tokens[i, :n] = full[r, :n]
            lens.append(n)
            cur.append(full[r, n] if s else 0)
        forced = {}
        for side, e in (("ref", ref_eng), ("got", eng)):
            pre, step = _teacher_forced(e, e.plan_key(len(rows)), tokens,
                                        lens, cur)
            forced[side] = np.stack([np.asarray(pre[i] if s == 0 else step[i],
                                                np.float64)
                                     for i, s in enumerate(steps)])
        for i, (r, s) in enumerate(zip(rows, steps)):
            rl, gl = forced["ref"][i], forced["got"][i]
            # ranks by value: bf16 logits tie often across a 151,936-way
            # vocabulary
            values = np.unique(rl)[::-1]
            g = int(got[r, s])
            rank = 1 + int(np.sum(values > rl[g]))
            trail = float(values[0] - rl[g])
            diff = float(np.max(np.abs(gl - rl)))
            log(f"[{tag}] B={b} row {r}: diverges at step {s} (token {g} "
                f"vs {int(want[r, s])}); reference top-2 margin "
                f"{float(values[0] - values[1]):.4f}, path's token ranks "
                f"{rank} and trails by {trail:.4f}, max |logit diff| at the "
                f"step {diff:.4f} (logit std {float(np.std(rl)):.4f})")
            check(trail <= 2 * diff, f"{tag}: B={b} row {r} step {s}: "
                  f"token trails by {trail:.4f} > 2 x max diff {diff:.4f}")
        compare_logits(tag, forced["got"], forced["ref"],
                       f"B={b} logits at the divergent steps")


def _teacher_forced(eng, key, tokens, lens, cur):
    """Each row's logits after ``tokens[:, :lens]`` through the group's
    paged prefill program (a new prompt bucket compiles a new program), and
    after one more token ``cur`` through the paged decode step."""
    import jax
    import jax.numpy as jnp
    from repro.launch import engine as E
    from repro.models import model as M
    from repro.models import paged as PG
    b, width = tokens.shape
    bucket = key.batch_bucket
    nb = PG.pages_for(width, eng.block_size)
    table = np.zeros((bucket, nb), np.int32)
    table[:b] = 1 + np.arange(b * nb, dtype=np.int32).reshape(b, nb)
    tok = np.zeros((bucket, width), np.int32)
    tok[:b] = tokens
    ln = np.zeros((bucket,), np.int32)
    ln[:b] = lens
    nxt = np.zeros((bucket, 1), np.int32)
    nxt[:b, 0] = cur
    tree = eng.serving_tree_for(key)
    pool = M.init_paged_pool(eng.cfg, 1 + bucket * nb, eng.block_size)
    pre, pool, _, _ = E._paged_prefill_dispatch(
        eng.cfg, eng.params, tree, jnp.asarray(tok), pool, jnp.asarray(table),
        jnp.asarray(ln))
    step, _ = jax.jit(M.paged_decode_step, static_argnums=0)(
        eng.cfg, eng.params, tree, {"tokens": jnp.asarray(nxt)}, pool,
        jnp.asarray(table), jnp.asarray(ln))
    return pre[:b], step[:b]


def peak_memory(tag: str) -> None:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    peak = stats.get("peak_bytes_in_use")
    limit = stats.get("bytes_limit")
    if peak is None:
        log(f"[{tag}] peak device memory: not reported by this backend")
    else:
        log(f"[{tag}] peak device memory {peak / 1e9:.3f} GB of "
            f"{(limit or 0) / 1e9:.3f} GB (device 0, since process start)")


# ---------------------------------------------------------------------------
# phases
# ---------------------------------------------------------------------------


def serve_phase(cfg, hbm: int, on_chip: bool):
    from repro.kernels import condensed_matmul as cm
    from repro.launch.engine import ServingEngine
    from repro.models import attention as A

    cfg = serve_storage(cfg, hbm, copies=1)
    reg, params, masks = _init_weights(cfg)
    log(f"[serve] {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"d_ff {cfg.d_ff}, vocab {cfg.vocab_size}, heads {cfg.n_heads}/"
        f"{cfg.n_kv_heads} x {cfg.head_dim}, compute {cfg.dtype}, storage "
        f"{cfg.param_dtype}, sparsity {cfg.sparsity.sparsity} "
        f"({cfg.sparsity.method}, {len(reg)} sparse stacks)")
    prompts = {1: _prompts(cfg, 1), 8: _prompts(cfg, 8)}
    out = {}
    for path in ("condensed", "masked"):
        eng = ServingEngine(cfg, params, masks, reg, path=path, warm=False)
        check(eng.paged, "qwen3-1.7b must run on the paged scheduler")
        res = serve_requests(eng, prompts, f"serve:{path}")
        key8 = eng.plan_key(8)
        logits, secs, cold = prefill_logits(eng, key8, prompts[8],
                                            [PROMPT_LEN] * 8)
        log(f"[serve:{path}] B=8 prefill re-dispatch {secs:.3f}s "
            f"(recompiled: {cold})")
        out[path] = (eng, res, logits)
        peak_memory(f"serve:{path}")

    eng, res, logits = out["condensed"]
    if on_chip:
        check(not cm.default_interpret(),
              "a Pallas kernel would run interpreted on the chip")
        t0 = time.perf_counter()
        text = decode_program_text(eng, eng.plan_key(8))
        n = text.count("tpu_custom_call")
        log(f"[serve:condensed] compiled decode program: {n} tpu_custom_call "
            f"site(s) ({time.perf_counter() - t0:.1f}s to fetch)")
        check(n > 0, "condensed decode program holds no Mosaic kernel")
    else:
        log("[serve:condensed] tpu_custom_call check: not on a TPU "
            "(interpreted kernels on the CPU)")
    log(f"[serve] attention paths traced: {A.path_counts()}")
    m_eng, m_res, m_logits = out["masked"]
    compare_logits("serve:condensed-vs-masked", logits, m_logits)
    compare_tokens("serve:condensed-vs-masked", res, m_res, eng, m_eng)


def train_phase(cfg, hbm: int):
    import dataclasses

    import jax
    import jax.numpy as jnp
    from repro.core import distributions as D
    from repro.data.pipeline import SyntheticLM
    from repro.models import attention as A
    from repro.sparse import registry as REG
    from repro.train.trainer import Trainer

    depth = train_depth(cfg, hbm)
    sp = dataclasses.replace(cfg.sparsity, delta_t=2)
    cfg = cfg.replace(n_layers=depth, sparsity=sp)
    steps = 3                       # DST update after step 2, one step after
    trainer = Trainer(cfg=cfg, lr_fn=lambda s: jnp.float32(1e-3), log_every=1)
    # no checkpoint directory: a failing step raises, never restores
    check(trainer.ckpt_dir is None, "the smoke trainer must not checkpoint")
    state = trainer.init_or_restore(jax.random.PRNGKey(SEED + 1))
    batch_size, seq = 8, PROMPT_LEN
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=seq,
                       batch_size=batch_size, seed=SEED, family=cfg.family,
                       d_model=cfg.d_model)
    batches = (jax.tree.map(jnp.asarray, data.batch(i)) for i in range(steps))
    losses = []

    def log_fn(msg):
        log(msg)
        m = re.search(r"loss (\S+)", msg)
        if m:
            losses.append(float(m.group(1)))

    log(f"[train] {cfg.name}: {depth} layers at d_model {cfg.d_model}, batch "
        f"{batch_size} x {seq}, {steps} steps, DST every {sp.delta_t} "
        f"(step times below are cold: compile included)")
    paths = A.path_counts()
    state = trainer.fit(state, batches, steps, log_fn=log_fn)
    paths = {k: v - paths[k] for k, v in A.path_counts().items()}
    log(f"[train] attention paths traced: {paths}")
    check(len(losses) == steps and all(math.isfinite(x) for x in losses),
          f"losses {losses}")
    versions = {k: int(v) for k, v in state.mask_versions.items()}
    log(f"[train] losses {losses}; mask versions after DST {versions}")
    check(any(versions.values()), "no DST update changed a mask")
    for s in REG.build_registry(cfg):
        k = D.fan_in_from_density(s.d_in, s.density)
        nnz = np.asarray(jnp.sum(REG.get_path(state.masks, s.path), axis=-2))
        active = np.asarray(REG.get_path(state.neuron_active, s.path))
        ok = bool(np.all(nnz[active] == k) and np.all(nnz[~active] == 0))
        log(f"[train] {s.name}: fan-in {sorted(set(nnz[active].tolist()))} "
            f"on {int(active.sum())} active of {active.size} neurons "
            f"(k={k}); constant: {ok}")
        check(ok, f"{s.name}: fan-in not constant after the DST update")
    peak_memory("train")


def tp_phase(cfg, hbm: int):
    import jax
    from repro.launch import hlo_analysis as HLO
    from repro.launch.engine import ServingEngine
    from repro.launch.mesh import make_serving_mesh
    from repro.sparse import registry as REG

    # the mesh serve.py --tp 4 builds
    mesh = make_serving_mesh(4)
    log(f"[tp4] mesh {dict(mesh.shape)}, axis types "
        f"{[t.name for t in mesh.axis_types]}")
    # device 0 holds the one-chip engine's copy and a replica of the mesh's
    cfg = serve_storage(cfg, hbm, copies=2)
    reg, params, masks = _init_weights(cfg)
    prompts = {8: _prompts(cfg, 8)}
    tp_eng = ServingEngine(cfg, params, masks, reg, path="condensed",
                           mesh=mesh, warm=False)
    # the reference is the one-chip masked path (dense MXU), which the
    # one-chip smoke compares the condensed kernel against
    one = ServingEngine(cfg, params, masks, reg, path="masked", warm=False)
    tp_res = serve_requests(tp_eng, prompts, "tp4:condensed")
    one_res = serve_requests(one, prompts, "tp1:masked")
    key = tp_eng.plan_key(8)
    plan = tp_eng.plan_for(key)
    sharded = []
    for s in reg:
        dec = plan.decisions[s.name]
        arrays = jax.tree.leaves(REG.get_path(plan.serving_tree, s.path))
        spans = sorted({len(a.sharding.device_set) for a in arrays})
        split = [a.shape for a in arrays if not a.sharding.is_fully_replicated]
        log(f"[tp4] {s.name}: {dec.representation} tp={dec.tp}, leaf arrays "
            f"span {spans} device(s), split over 'model': {split}")
        if dec.tp > 1:
            check(spans == [4] and split,
                  f"{s.name}: sharded leaf not split over 4 devices")
            sharded.append(s.name)
    check(any(plan.decisions[n].tp == 4 for n in sharded),
          "no stack sharded 4 ways")
    text = decode_program_text(tp_eng, key)
    counts = HLO.analyze(text).count_by_type
    log(f"[tp4] compiled decode program collectives: "
        f"{ {k: v for k, v in counts.items() if v} }")
    check(counts["all-gather"] > 0, "TP decode program has no all-gather")
    logits, _, _ = prefill_logits(tp_eng, key, prompts[8], [PROMPT_LEN] * 8)
    ref, _, _ = prefill_logits(one, one.plan_key(8), prompts[8],
                               [PROMPT_LEN] * 8)
    compare_logits("tp4-vs-tp1", logits, ref)
    compare_tokens("tp4-vs-tp1", tp_res, one_res, tp_eng, one)
    peak_memory("tp4")


def aot_phase(cfg, hbm: int):
    """Compile the full-width paged prefill and decode-chunk programs of
    both serving paths, and the train phase's step and DST programs, for a
    described v5e chip; print memory_analysis()."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro.kernels import condensed_matmul as cm
    from repro.launch import engine as E
    from repro.models import attention as A
    from repro.models import model as M
    from repro.models import paged as PG
    from repro.sparse import plan as PLAN
    from repro.sparse import registry as REG

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    # this process runs on the CPU, where the kernels would pick interpret
    # mode and attention its chunked scan; the program compiled here is the
    # chip's, so steer them
    cm.default_interpret = lambda backend=None: False
    A._on_tpu = lambda: True

    def on_chip(tree):
        return jax.tree.map(
            lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
            tree)

    train_cfg = cfg
    cfg = serve_storage(cfg, hbm, copies=1)
    reg = REG.build_registry(cfg)
    params = on_chip(jax.eval_shape(
        lambda k: M.init_params(cfg, k, REG.k_fan_map(cfg, reg)),
        jax.random.PRNGKey(0)))
    masks = on_chip(jax.eval_shape(
        lambda k: REG.init_sparsity_state(cfg, k, reg)["masks"],
        jax.random.PRNGKey(0)))
    bucket, bs = 8, 16
    nb = PG.pages_for(PROMPT_LEN + GEN_LEN, bs)
    pool = on_chip(jax.eval_shape(
        lambda: M.init_paged_pool(cfg, 1 + bucket * nb, bs)))
    table = jax.ShapeDtypeStruct((bucket, nb), jnp.int32, sharding=chip)
    lens = jax.ShapeDtypeStruct((bucket,), jnp.int32, sharding=chip)
    toks = jax.ShapeDtypeStruct((bucket, PROMPT_LEN), jnp.int32, sharding=chip)
    cur = jax.ShapeDtypeStruct((bucket, 1), jnp.int32, sharding=chip)
    trees = {"masked": masks,
             "condensed": on_chip(PLAN.abstract_serving_tree(
                 cfg, reg, {s.name: "condensed" for s in reg}))}
    for path, tree in trees.items():
        for prog, lower in (
                ("prefill", lambda: E._paged_prefill.lower(
                    cfg, params, tree, {"tokens": toks}, pool, table, lens)),
                ("decode", lambda: E._paged_decode_chunk.lower(
                    cfg, params, tree, pool, table, lens, cur, 16))):
            t0 = time.perf_counter()
            compiled = lower().compile()
            ma = compiled.memory_analysis()
            n = compiled.as_text().count("tpu_custom_call")
            log(f"[aot] {path} {prog} (B={bucket}, T={PROMPT_LEN}): compiled "
                f"for v5e in {time.perf_counter() - t0:.1f}s; "
                f"tpu_custom_call sites {n}; arguments "
                f"{ma.argument_size_in_bytes / 1e9:.3f} GB, outputs "
                f"{ma.output_size_in_bytes / 1e9:.3f} GB, temporaries "
                f"{ma.temp_size_in_bytes / 1e9:.3f} GB, aliased "
                f"{ma.alias_size_in_bytes / 1e9:.3f} GB")
            if path == "condensed":
                check(n > 0, "condensed program compiled without a kernel")

    # the train phase's two programs at the depth its reckoning picks
    import dataclasses

    from repro.data.pipeline import SyntheticLM
    from repro.train.state import init_train_state
    from repro.train.trainer import make_dst_step, make_train_step
    tcfg = train_cfg.replace(
        n_layers=train_depth(train_cfg, hbm),
        sparsity=dataclasses.replace(train_cfg.sparsity, delta_t=2))
    treg = REG.build_registry(tcfg)
    state = on_chip(jax.eval_shape(lambda k: init_train_state(tcfg, k),
                                   jax.random.PRNGKey(0)))
    data = SyntheticLM(vocab_size=tcfg.vocab_size, seq_len=PROMPT_LEN,
                       batch_size=8, seed=SEED, family=tcfg.family,
                       d_model=tcfg.d_model)
    batch = on_chip(jax.tree.map(jnp.asarray, data.batch(0)))
    for prog, fn in (
            ("train_step", make_train_step(tcfg, treg,
                                           lambda s: jnp.float32(1e-3))),
            ("dst_step", make_dst_step(tcfg, treg))):
        t0 = time.perf_counter()
        ma = jax.jit(fn, donate_argnums=(0,)).lower(state, batch).compile() \
            .memory_analysis()
        log(f"[aot] {prog} ({tcfg.n_layers} layers): compiled for v5e in "
            f"{time.perf_counter() - t0:.1f}s; arguments "
            f"{ma.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
            f"{ma.temp_size_in_bytes / 1e9:.3f} GB, aliased "
            f"{ma.alias_size_in_bytes / 1e9:.3f} GB")


def main(argv=None) -> int:
    args = parse_args(argv)
    if args.rehearse and args.chips == 4:
        os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "") + " "
                                   "--xla_force_host_platform_device_count=4")
    if args.aot:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
    # default kernel blocks: no autotune cache is read (nothing writes here)
    os.environ["REPRO_AUTOTUNE_CACHE"] = str(ROOT / ".jax_cache"
                                             / "no-autotune.json")
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    from repro import configs
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    dev = devices[0]
    log(f"[device] {dev.platform} {dev.device_kind} x {len(devices)} "
        f"(jax {jax.__version__})")
    if not (args.rehearse or args.aot) and dev.platform != "tpu":
        log(f"[device] FAILED: platform {dev.platform!r}, this smoke runs "
            "on a TPU (use --rehearse for the CPU rehearsal)")
        return 1
    if (args.rehearse or args.aot) and dev.platform != "cpu":
        log("[device] FAILED: --rehearse/--aot run on the CPU; set "
            "JAX_PLATFORMS=cpu")
        return 1
    if len(devices) < args.chips:
        log(f"[device] FAILED: {args.chips} chips asked, {len(devices)} found")
        return 1

    # a compile for a described chip is written to the cache but cannot be
    # read back without one: the AOT rehearsal keeps the cache off
    cache_dir = None if args.aot else enable_compile_cache()
    if cache_dir:
        log(f"[cache] compilation cache {cache_dir}: "
            f"{cache_entries(cache_dir)} entries before")
    smoke = Smoke()
    cfg = (configs.get_smoke_config if args.rehearse
           else configs.get_config)(ARCH)
    stats = dev.memory_stats() or {}
    # a v5e chip holds 16 GB of HBM (what --aot and --rehearse assume)
    hbm = int(stats.get("bytes_limit") or 16 * 10**9)
    log(f"[device] memory limit {hbm / 1e9:.2f} GB"
        + ("" if "bytes_limit" in stats else " (assumed: not reported)"))

    if args.aot:
        smoke.run("aot", aot_phase, cfg, hbm)
    elif args.chips == 4:
        smoke.run("tp4", tp_phase, cfg, hbm)
    else:
        smoke.run("serve", serve_phase, cfg, hbm, not args.rehearse)
        smoke.run("train", train_phase, cfg, hbm)
    if cache_dir:
        log(f"[cache] {cache_dir}: {cache_entries(cache_dir)} entries after")

    if smoke.failed:
        log(f"[smoke] FAILED phases: {smoke.failed}")
        return 1
    if args.rehearse or args.aot:
        log("[smoke] rehearsal passed (CPU): no chip was used, no ok line")
        return 0
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
