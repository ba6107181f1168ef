"""Training runtime: optimizer masking, DST-in-the-loop, checkpoint/restart."""
import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.core import topology
from repro.data.pipeline import SyntheticLM
from repro.optim import make_optimizer
from repro.sparse import registry as REG
from repro.train import checkpoint as CKPT
from repro.train.state import init_train_state
from repro.train.trainer import Trainer, make_dst_step, make_train_step


def _cfg(name="qwen3-1.7b", **sp):
    cfg = configs.get_smoke_config(name)
    return cfg.replace(sparsity=dataclasses.replace(cfg.sparsity, **sp))


def _batches(cfg, n, bsz=4, seq=32):
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=seq, batch_size=bsz,
                       seed=0, family=cfg.family, n_codebooks=cfg.n_codebooks,
                       d_model=cfg.d_model)
    return [jax.tree.map(jnp.asarray, data.batch(i)) for i in range(n)]


def test_optimizer_respects_masks():
    """Pruned weights never move; active weights do."""
    cfg = _cfg(delta_t=10_000)  # no DST updates in this test
    reg = REG.build_registry(cfg)
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, reg, lambda s: jnp.float32(1e-2)))
    w0 = REG.get_path(state.params, reg[0].path)
    m = REG.get_path(state.masks, reg[0].path)
    for b in _batches(cfg, 3):
        state, _ = step(state, b)
    w1 = REG.get_path(state.params, reg[0].path)
    diff = np.abs(np.array(w1 - w0))
    assert diff[~np.array(m)].max() == 0.0       # pruned slots frozen
    assert diff[np.array(m)].max() > 0.0         # active slots trained


def test_dst_step_maintains_invariants_and_zeroes_grown():
    cfg = _cfg(delta_t=5)
    reg = REG.build_registry(cfg)
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, reg, lambda s: jnp.float32(3e-3)))
    dst = jax.jit(make_dst_step(cfg, reg))
    batches = _batches(cfg, 12)
    for i, b in enumerate(batches):
        state, _ = step(state, b)
        if (i + 1) % 5 == 0:
            old_masks = jax.tree.map(lambda x: x, state.masks)
            state = dst(state, b)
            for s in reg:
                m_new = np.array(REG.get_path(state.masks, s.path))
                m_old = np.array(REG.get_path(old_masks, s.path))
                w = np.array(REG.get_path(state.params, s.path))
                grown = m_new & ~m_old
                if grown.any():
                    assert np.abs(w[grown]).max() == 0.0  # regrown start at 0
                a = np.array(REG.get_path(state.neuron_active, s.path))
                m2 = m_new.reshape(-1, *m_new.shape[-2:])
                a2 = a.reshape(-1, a.shape[-1])
                for j in range(m2.shape[0]):
                    nnz = m2[j].sum(0)
                    k = nnz[a2[j]].max() if a2[j].any() else 0
                    assert topology.check_constant_fan_in(m2[j], int(k), a2[j])


def test_dst_step_stamps_mask_versions():
    """The trainer's per-stack mask-version counters (consumed by the serving
    Plan's incremental export): train_step leaves them alone; the DST step
    bumps exactly the stacks whose masks actually changed."""
    cfg = _cfg(delta_t=2)
    reg = REG.build_registry(cfg)
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    assert set(state.mask_versions) == {s.name for s in reg}
    assert all(int(v) == 0 for v in state.mask_versions.values())

    step = jax.jit(make_train_step(cfg, reg, lambda s: jnp.float32(3e-3)))
    dst = jax.jit(make_dst_step(cfg, reg))
    for b in _batches(cfg, 2):
        state, _ = step(state, b)
    assert all(int(v) == 0 for v in state.mask_versions.values())  # no DST yet

    old_masks = jax.tree.map(lambda x: x, state.masks)
    state = dst(state, _batches(cfg, 1)[0])
    for s in reg:
        changed = bool(np.any(np.array(REG.get_path(state.masks, s.path))
                              != np.array(REG.get_path(old_masks, s.path))))
        assert int(state.mask_versions[s.name]) == int(changed)


def test_loss_decreases_with_dst():
    cfg = _cfg(delta_t=5)
    trainer = Trainer(cfg=cfg, lr_fn=lambda s: jnp.float32(3e-3), log_every=1000)
    state = trainer.init_or_restore(jax.random.PRNGKey(0))
    data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32, batch_size=4, seed=0)
    batches = (jax.tree.map(jnp.asarray, data.batch(i)) for i in range(10_000))
    state = trainer.fit(state, batches, 50, log_fn=lambda *_: None)
    # measure directly
    step = jax.jit(make_train_step(cfg, trainer.registry, lambda s: jnp.float32(0.0)))
    _, m = step(state, jax.tree.map(jnp.asarray, data.batch(0)))
    assert float(m["loss"]) < 5.4  # init CE is ~ln(256)=5.55


def test_rigl_and_set_methods_run():
    for method in ("rigl", "set"):
        cfg = _cfg(delta_t=3)
        cfg = cfg.replace(sparsity=dataclasses.replace(cfg.sparsity, method=method))
        reg = REG.build_registry(cfg)
        state = init_train_state(cfg, jax.random.PRNGKey(0))
        step = jax.jit(make_train_step(cfg, reg, lambda s: jnp.float32(1e-3)))
        dst = jax.jit(make_dst_step(cfg, reg))
        for i, b in enumerate(_batches(cfg, 4)):
            state, metrics = step(state, b)
            if (i + 1) % 3 == 0:
                state = dst(state, b)
        assert bool(jnp.isfinite(metrics["loss"]))


def test_dense_method_no_masks():
    cfg = _cfg().replace(sparsity=dataclasses.replace(
        configs.get_smoke_config("qwen3-1.7b").sparsity, method="dense"))
    reg = REG.build_registry(cfg)
    assert reg == []
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    step = jax.jit(make_train_step(cfg, reg, lambda s: jnp.float32(1e-3)))
    state, m = step(state, _batches(cfg, 1)[0])
    assert bool(jnp.isfinite(m["loss"]))


def test_grad_accum_saliency_window():
    cfg = _cfg(delta_t=4, grad_accum_for_saliency=4)
    reg = REG.build_registry(cfg)
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    assert state.grad_accum  # accumulator allocated
    step = jax.jit(make_train_step(cfg, reg, lambda s: jnp.float32(1e-3)))
    dst = jax.jit(make_dst_step(cfg, reg))
    for i, b in enumerate(_batches(cfg, 8)):
        state, _ = step(state, b)
        if (i + 1) % 4 == 0:
            state = dst(state, b)
    acc = REG.get_path(state.grad_accum, reg[0].path)
    assert bool(jnp.isfinite(acc).all())


def test_checkpoint_restart_resumes_exactly():
    cfg = _cfg(delta_t=100)
    with tempfile.TemporaryDirectory() as d:
        tr = Trainer(cfg=cfg, lr_fn=lambda s: jnp.float32(1e-3), ckpt_dir=d,
                     ckpt_every=5, log_every=1000)
        state = tr.init_or_restore(jax.random.PRNGKey(0))
        data = SyntheticLM(vocab_size=cfg.vocab_size, seq_len=32, batch_size=4, seed=0)

        def batches(start):
            i = start
            while True:
                yield jax.tree.map(jnp.asarray, data.batch(i))
                i += 1

        state = tr.fit(state, batches(0), 10, log_fn=lambda *_: None)
        # simulate crash: fresh trainer restores from step 10
        tr2 = Trainer(cfg=cfg, lr_fn=lambda s: jnp.float32(1e-3), ckpt_dir=d,
                      log_every=1000)
        restored = tr2.init_or_restore(jax.random.PRNGKey(42))
        assert int(restored.step) == 10
        for (ka, a), (kb, b) in zip(
                sorted(CKPT._flatten(state._asdict()).items()),
                sorted(CKPT._flatten(restored._asdict()).items())):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b), err_msg=ka)


def test_fit_counts_each_host_sync():
    """``fit`` turns a device value into a Python value once per call (the
    step counter), once per step (is the DST update due?) and once per
    logged step (the loss); each is counted where it happens."""
    cfg = _cfg(delta_t=3)
    tr = Trainer(cfg=cfg, lr_fn=lambda s: jnp.float32(1e-3), log_every=2)
    state = init_train_state(cfg, jax.random.PRNGKey(0))
    batches = _batches(cfg, 7)
    quiet = lambda *_: None
    state = tr.fit(state, iter(batches[:5]), 5, log_fn=quiet)
    assert tr.host_syncs == 1 + 5 + 3               # steps 0, 2, 4 logged
    tr.fit(state, iter(batches[5:]), 2, log_fn=quiet)
    assert tr.host_syncs == 9 + 1 + 2 + 1           # step 6 logged


@pytest.mark.parametrize("opt", ["sgdm", "adamw", "adafactor"])
def test_optimizers_step(opt):
    init, update = make_optimizer(opt)
    params = {"a": {"w": jnp.ones((8, 4))}, "b": jnp.ones((4,))}
    grads = jax.tree.map(jnp.ones_like, params)
    st = init(params)
    p1, st1 = update(params, grads, st, 0.1)
    assert float(p1["a"]["w"][0, 0]) < 1.0
    # masked variant: masked slots unchanged
    masks = {"a": {"w": jnp.zeros((8, 4), bool).at[0].set(True)}}
    p2, _ = update(params, grads, st, 0.1, masks=masks)
    assert float(p2["a"]["w"][1, 0]) == 1.0
    assert float(p2["a"]["w"][0, 0]) < 1.0


def test_elastic_mesh_helper():
    from repro.train.elastic import largest_feasible_mesh
    assert largest_feasible_mesh(256, 16) == (16, 16)
    assert largest_feasible_mesh(240, 16) == (15, 16)
    assert largest_feasible_mesh(8, 16) == (1, 16)
