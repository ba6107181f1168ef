"""The chunked cross-entropy head computes its gradient in its forward sweep.

``model.cross_entropy_chunked`` is a custom VJP: each chunk's logits give
the loss and, at once, dlogits, dh and the head's dW, so nothing is kept or
recomputed for the backward pass. These tests compare its loss and
gradients with a plain float32 full-logits cross-entropy and with the
rematerialised scan it replaced (kept here as a reference), and pin the
mechanism in the compiled train and DST programs: three matmuls over the
vocabulary in the train step (the remat'd scan had four), two in the DST
program (the head's dW is dead there), and no scatter of logits shape.
"""
import json
import os
import pathlib
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.data.pipeline import SyntheticLM
from repro.launch import hlo_analysis as H
from repro.models import model as M
from repro.sparse import registry as REG
from repro.train.state import init_train_state
from repro.train.trainer import make_dst_step, make_train_step

D, V = 64, 256                  # the rehearsal size of the train-dst cell


def plain_ce(hidden, head, targets, mask, valid, tied):
    """Mean CE over float32 (B, T, V) logits at full matmul precision."""
    w = (head.T if tied else head).astype(jnp.float32)
    logits = jnp.einsum("btd,dv->btv", hidden.astype(jnp.float32), w,
                        precision=jax.lax.Precision.HIGHEST)
    logits = jnp.where(jnp.arange(w.shape[1]) < valid, logits, -1e30)
    nll = (jax.nn.logsumexp(logits, -1)
           - jnp.take_along_axis(logits, targets[..., None], -1)[..., 0])
    return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def remat_scan_ce(hidden, head, targets, mask, valid, tied, chunk):
    """The head before the fused sweep: a scan whose chunk body is
    rematerialised, so the backward pass recomputes each chunk's logits."""
    lm_head = head.T if tied else head
    b, t, d = hidden.shape
    nc = -(-t // chunk)
    pad = nc * chunk - t
    hidden = jnp.pad(hidden, ((0, 0), (0, pad), (0, 0)))
    targets = jnp.pad(targets, ((0, 0), (0, pad)))
    lm = jnp.pad(mask, ((0, 0), (0, pad)))
    hc = hidden.reshape(b, nc, chunk, d).transpose(1, 0, 2, 3)
    tc = targets.reshape(b, nc, chunk).transpose(1, 0, 2)
    mc = lm.reshape(b, nc, chunk).transpose(1, 0, 2)
    v_total = lm_head.shape[-1]

    @jax.checkpoint
    def step(carry, xs):
        tot, cnt = carry
        h_i, t_i, m_i = xs
        logits = (h_i @ lm_head.astype(h_i.dtype)).astype(jnp.float32)
        if valid != v_total:
            logits = jnp.where(jnp.arange(v_total) < valid, logits, -1e30)
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, t_i[..., None], axis=-1)[..., 0]
        nll = (lse - gold) * m_i
        return (tot + jnp.sum(nll), cnt + jnp.sum(m_i)), None

    (tot, cnt), _ = jax.lax.scan(step, (jnp.zeros(()), jnp.zeros(())),
                                 (hc, tc, mc))
    return tot / jnp.maximum(cnt, 1.0)


# name -> (T, chunk, loss mask with zeros, valid vocabulary, tied,
#          codebooks, differentiated, compute dtype)
CASES = {
    "ragged_T": (40, 16, False, V, False, 0, True, jnp.float32),
    "loss_mask": (48, 16, True, V, False, 0, True, jnp.float32),
    "valid_vocab": (48, 16, False, 250, False, 0, True, jnp.float32),
    "tied": (40, 16, True, 250, True, 0, True, jnp.float32),
    "audio_codebooks": (40, 16, False, 250, False, 2, True, jnp.float32),
    "primal_only": (40, 16, True, 250, True, 0, False, jnp.float32),
    "tied_bf16": (40, 16, True, 250, True, 0, True, jnp.bfloat16),
}
# relative gaps (norm of the difference over the reference's norm): f32
# sums reorder within a few eps; bf16 operands round each logit and dlogit
# to 2^-8, and the remat'd scan also rounded each chunk's dW to bf16
GAP = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


def _gap(a, r):
    a, r = np.asarray(a, np.float64), np.asarray(r, np.float64)
    return float(np.linalg.norm(a - r) / max(np.linalg.norm(r), 1e-30))


@pytest.mark.parametrize("case", CASES)
def test_fused_head_matches_references(case):
    t, chunk, masked, valid, tied, books, grad, dtype = CASES[case]
    b = 2
    k = jax.random.split(jax.random.PRNGKey(7), 4)
    hidden = jax.random.normal(k[0], (b, t, D), jnp.float32).astype(dtype)
    shape = (V, D) if tied else (max(books, 1), D, V)
    head = 0.3 * jax.random.normal(k[1], shape, jnp.float32)
    targets = jax.random.randint(k[2], (b, max(books, 1), t), 0, valid)
    mask = (jax.random.uniform(k[3], (b, t)) > 0.3).astype(jnp.float32) \
        if masked else jnp.ones((b, t), jnp.float32)
    if masked:
        assert 0 < float(mask.sum()) < mask.size

    def head_loss(impl):
        """The loss_fn call shape: the tied (V, d) table, or one (d, V)
        head per codebook averaged as the audio family does."""
        def f(h, w):
            if tied:
                return impl(h, w, targets[:, 0], mask, True)
            return sum(impl(h, w[c], targets[:, c], mask, False)
                       for c in range(w.shape[0])) / w.shape[0]
        return f

    new = head_loss(lambda h, w, tg, m, td: M.cross_entropy_chunked(
        h, w, tg, chunk, m if masked else None, valid_vocab=valid,
        vocab_rows=td))
    refs = {
        "plain_f32": head_loss(lambda h, w, tg, m, td: plain_ce(
            h, w, tg, m, valid, td)),
        "remat_scan": head_loss(lambda h, w, tg, m, td: remat_scan_ce(
            h, w, tg, m, valid, td, chunk)),
    }
    tol = GAP[dtype]
    if not grad:
        loss = jax.jit(new)(hidden, head)
        # the undifferentiated call is the loss alone: no head-sized dW
        text = jax.jit(new).lower(hidden, head).compile().as_text()
        assert len(H.matmuls_spanning(text, V)) == 1
        for name, ref in refs.items():
            assert _gap(loss, jax.jit(ref)(hidden, head)) < tol, name
        return
    loss, (dh, dw) = jax.jit(jax.value_and_grad(new, argnums=(0, 1)))(
        hidden, head)
    assert dh.dtype == hidden.dtype and dw.dtype == head.dtype
    for name, ref in refs.items():
        r_loss, (r_dh, r_dw) = jax.jit(jax.value_and_grad(
            ref, argnums=(0, 1)))(hidden, head)
        assert _gap(loss, r_loss) < tol, (name, "loss")
        assert _gap(dh, r_dh) < tol, (name, "hidden")
        assert _gap(dw, r_dw) < tol, (name, "head")
    if valid < V:  # padded vocabulary columns get no gradient
        pad_cols = dw[valid:] if tied else dw[..., valid:]
        assert not np.asarray(pad_cols).any()


@pytest.fixture(scope="module")
def compiled_programs():
    """The rehearsal-size train step and DST program, compiled for the CPU
    at T 40 (three 16-position chunks, the last one padded)."""
    cfg = configs.get_smoke_config("qwen3-1.7b")
    assert cfg.vocab_size == V and V not in (cfg.d_model, cfg.d_ff)
    reg = REG.build_registry(cfg)
    state = jax.eval_shape(lambda key: init_train_state(cfg, key),
                           jax.random.PRNGKey(0))
    data = SyntheticLM(vocab_size=V, seq_len=40, batch_size=2, seed=0,
                       family=cfg.family, d_model=cfg.d_model)
    batch = jax.tree.map(jnp.asarray, data.batch(0))
    fns = {"train": make_train_step(cfg, reg, lambda s: jnp.float32(1e-3)),
           "dst": make_dst_step(cfg, reg)}
    return {name: jax.jit(fn).lower(state, batch).compile().as_text()
            for name, fn in fns.items()}


@pytest.mark.parametrize("program,passes", [("train", 3), ("dst", 2)])
def test_head_passes_in_compiled_programs(compiled_programs, program, passes):
    """Train step: logits, dh and dW, once each. DST program: the
    embedding's gradient is discarded, so the dW matmul is pruned."""
    text = compiled_programs[program]
    matmuls = H.matmuls_spanning(text, V)
    assert len(matmuls) == passes, [m.name for m in matmuls]
    # all in the head's scope, none under a rematerialisation
    for m in matmuls:
        assert "(head)/" in m.attrs and "rematted" not in m.attrs, m.attrs
    # dlogits come from a comparison with the targets, not from the
    # scatter that take_along_axis transposes into
    assert not [s for s in H.instruction_shapes(text, "scatter")
                if len(s) >= 3 and s[-1] == V]


_TP_PROGRAM = r"""
import json
import jax, jax.numpy as jnp, numpy as np
from repro import configs
from repro.launch import hlo_analysis as H
from repro.launch.mesh import make_mesh
from repro.models import model as M

cfg = configs.get_smoke_config("qwen3-1.7b")
# d differs from the vocabulary's quarter, so the per-device shapes tell
b, t, d, v = 2, 40, 48, cfg.vocab_size
k = jax.random.split(jax.random.PRNGKey(3), 3)
hidden = jax.random.normal(k[0], (b, t, d))
table = 0.3 * jax.random.normal(k[1], (v, d))
targets = jax.random.randint(k[2], (b, t), 0, 250)

def f(h, w):
    return M.cross_entropy_chunked(h, w, targets, 16, valid_vocab=250,
                                   cfg=cfg, vocab_rows=True)

step = jax.value_and_grad(f, argnums=(0, 1))
ref = jax.jit(step)(hidden, table)
mesh = make_mesh((1, 4), ("data", "model"))
with jax.set_mesh(mesh):
    fn = jax.jit(step)
    text = fn.lower(hidden, table).compile().as_text()
    got = fn(hidden, table)

def gap(a, r):
    a, r = np.asarray(a, np.float64), np.asarray(r, np.float64)
    return float(np.linalg.norm(a - r) / np.linalg.norm(r))

print(json.dumps({
    "gaps": [gap(a, r) for a, r in zip(jax.tree.leaves(got),
                                       jax.tree.leaves(ref))],
    "full_vocab": len(H.matmuls_spanning(text, v)),
    "vocab_shard": len(H.matmuls_spanning(text, v // 4)),
}))
"""


def test_vocab_sharded_head_matches_unsharded(tmp_path):
    """On a (1, 4) mesh the head's vocabulary is sharded over 'model'
    (``vocab_hint``): each device forms logits for a quarter of it, and the
    loss and both gradients equal the one-device run's."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4",
               PYTHONPATH=str(pathlib.Path(__file__).resolve().parents[1]
                              / "src"))
    run = subprocess.run([sys.executable, "-c", _TP_PROGRAM], env=env,
                         capture_output=True, text=True, timeout=300,
                         cwd=tmp_path)
    assert run.returncode == 0, run.stderr[-2000:]
    out = json.loads(run.stdout.splitlines()[-1])
    # logits, dh and dW each over a quarter of the vocabulary, none over all
    assert (out["full_vocab"], out["vocab_shard"]) == (0, 3)
    assert len(out["gaps"]) == 3 and max(out["gaps"]) < GAP[jnp.float32]
