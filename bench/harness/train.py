"""Training loop: SRigL training through the program's ``Trainer``.

Set-up makes the weights and masks from the seed, builds one ``TrainState``
and one ``Trainer``, and drives them through the job's first three steps on
rows that all differ. The readings the check compares are taken there: the
loss of each step, each leaf's first gradient as the optimizer took it (the
first moment after one step over 1 - b1), and each leaf's change after the
three steps. The DST program is compiled ahead of the window, which then
hands the same state and trainer on, one step per ``fit`` call, until
``--seconds`` have passed. The job's step counter starts so that the
window's first step ends in the DST update: every window holds it at the
same place, and the masks it made are kept for the check.

After the window the plain reference (``harness.reference``) runs the same
steps from the same weights, the fourth included, recomputes the dense
gradient there and makes its own topology update. The gaps are compared
leaf by leaf, and the update matrix by matrix (each index of a stack's
leading dims is one), with the traffic file's limits. Fan-in must be
constant on every active neuron after the window. The ``Trainer``'s
counters over the window are handed on as ``program.<name>``.

With ``--control`` or ``--fault`` the program is not run: the reference put
in its place (in fp8, on half of each batch, or regrowing at random) gives
the readings.
"""
from __future__ import annotations

import functools
import gc

import jax
import jax.numpy as jnp
import numpy as np

from harness import archs, core, program, reference
from harness import trace as TR
from harness import weights as W
from harness.core import Check, Outcome, log, now

CHECKED_STEPS = 3


@functools.partial(jax.jit, static_argnums=(2, 3, 4))
def _batch(key, i, rows, seq, vocab):
    t = jax.random.randint(jax.random.fold_in(key, i), (rows, seq + 1), 0,
                           vocab, jnp.int32)
    return {"tokens": t[:, :-1], "targets": t[:, 1:]}


class Feed:
    """Step i's batch: rows drawn from the seed and the step number."""

    def __init__(self, seed, rows, seq, vocab):
        self.key = jax.random.fold_in(W.key_from_seed(seed), 7)
        self.rows, self.seq, self.vocab = rows, seq, vocab
        self.i = 0

    def __iter__(self):
        return self

    def __next__(self):
        b = _batch(self.key, self.i, self.rows, self.seq, self.vocab)
        self.i += 1
        return b


def _leaf_norms(tree) -> dict[str, float]:
    flat = dict(reference.leaves(tree))
    norms = jax.jit(lambda t: {k: jnp.linalg.norm(v.astype(jnp.float32))
                               for k, v in t.items()})(flat)
    return {k: float(v) for k, v in norms.items()}


def _change_norms(p_new, p_old) -> dict[str, float]:
    a, b = dict(reference.leaves(p_new)), dict(reference.leaves(p_old))
    norms = jax.jit(lambda x, y: {k: jnp.linalg.norm(x[k] - y[k])
                                  for k in x})(a, b)
    return {k: float(v) for k, v in norms.items()}


def _state(cfg, reg, params, masks, step0, key):
    from repro.optim import make_optimizer
    from repro.sparse import registry as REG
    from repro.train.state import TrainState
    opt_init, _ = make_optimizer(cfg.optimizer)
    active = {}
    for s in reg:
        REG.set_path(active, s.path, jnp.ones((*s.lead, s.d_out), bool))
    return TrainState(
        step=jnp.asarray(step0, jnp.int32), params=params,
        opt_state=opt_init(params), masks=masks, neuron_active=active,
        grad_accum={}, mask_versions={s.name: jnp.zeros((), jnp.int32)
                                      for s in reg},
        rng=key)


def fan_in_faults(model, masks, active) -> int:
    """Sparse matrices (each index of a stack's leading dims is one) in
    which an active neuron's fan-in is not the configuration's k, or an
    ablated neuron keeps inputs."""
    bad = 0
    for path, k in W.fan_ins(model).items():
        name = "/".join(path)
        nnz = np.asarray(jnp.sum(reference.at(masks, name), axis=-2))
        on = np.asarray(reference.at(active, name))
        ok = np.where(on, nnz == k, nnz == 0).all(axis=-1)
        bad += int(np.sum(~ok))
    return bad


def run(r: core.Run) -> Outcome:
    if r.control or r.fault:
        return _readings_only(r)
    from repro.train.trainer import Trainer
    model, tr = r.model, r.traffic
    rows, seq = int(tr["batch"]), int(tr["seq_len"])
    delta_t = int(model["sparsity"]["delta_t"])
    step0 = delta_t - 1 - CHECKED_STEPS
    opt = model["optimizer"]
    lr = float(opt["lr"])
    arch = archs.of(model)
    cfg = program.arch_config(model, dtype=model["compute_dtype"],
                              param_dtype=model["param_dtype"])
    params, masks = W.make(model, model["param_dtype"], r.seed)
    reg = program.check_layout(cfg, model, params, masks)
    state = _state(cfg, reg, params, masks, step0,
                   jax.random.fold_in(W.key_from_seed(r.seed), 3))
    del params, masks
    trainer = Trainer(cfg=cfg, lr_fn=lambda s: jnp.float32(lr), log_every=1)
    feed = Feed(r.seed, rows, seq, model["vocab_size"])
    losses: list[float] = []

    def capture(msg):
        if " loss " in msg:
            losses.append(float(msg.split(" loss ")[1].split()[0]))

    state = trainer.fit(state, feed, 1, log_fn=capture)
    first = {k: v / (1 - opt["b1"])
             for k, v in _leaf_norms(state.opt_state["mu"]).items()}
    state = trainer.fit(state, feed, CHECKED_STEPS - 1, log_fn=capture)
    p0, _ = W.make(model, model["param_dtype"], r.seed)
    change = _change_norms(state.params, p0)
    del p0
    gc.collect()
    # the window's programs not yet run: the DST update and the copy of the
    # masks it makes
    trainer._dst_fn.lower(state, _batch(feed.key, 0, rows, seq,
                                        model["vocab_size"])).compile()
    jax.block_until_ready(jax.tree.map(jnp.copy, state.masks))
    trainer.log_every = 50
    counter = core.CompileCounter()
    tracer = TR.Tracer(r.trace)
    quiet = lambda msg: None
    counted = program.COUNTERS + arch.COUNTERS
    before = program.counters(trainer, counted)

    setup_s = now() - r.t_start
    counter.active = True
    tracer.start()
    t_open = now()
    steps = dst = 0
    updated = None                  # the masks the window's DST update made
    while True:
        i = int(state.step)
        due = (i + 1) % delta_t == 0
        # a fit call whose step is followed by the DST update is its span
        with tracer.span("bench.dst_step" if due else "bench.train_step"):
            state = trainer.fit(state, feed, 1, log_fn=quiet)
        if due and updated is None:
            updated = jax.tree.map(jnp.copy, state.masks)
        steps += 1
        dst += due
        t = now()
        if t - t_open >= r.seconds:
            break
    jax.block_until_ready(state.params)
    t_close = now()
    tracer.stop()
    counter.active = False
    window = t_close - t_open
    metrics = {"setup_s": setup_s,
               "train_tok_s": steps * rows * seq / window}
    log(f"[train] window {window:.3f} s: {steps} steps of {rows} x {seq} "
        f"tokens, {dst} DST update(s) in the window, programs compiled in "
        f"the window: {counter.count}")
    fan_faults = fan_in_faults(model, state.masks, state.neuron_active)
    if updated is None:             # no update ran: the masks as they stand
        updated = state.masks
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    # the program's counters over the window
    counters = {k: v - before[k] for k, v in
                program.counters(trainer, counted).items()}
    counters |= {"steps": steps, "dst_updates": dst, "window_s": window}
    counters["flops"] = steps * arch.flops_per_step(model, rows, seq,
                                                    counters)
    del state, trainer
    gc.collect()
    live = sum(a.nbytes for a in jax.live_arrays())
    use = (jax.devices()[0].memory_stats() or {}).get("bytes_in_use")
    log(f"[train] before the reference: live arrays {live} B, device bytes "
        f"in use {use}")

    ref = reference_readings(model, opt, r.seed, rows, seq, None)
    checks = _checks(model, tr, (losses, first, change, updated), ref,
                     r.seed)
    checks.append(Check("fan_in_faults", float(fan_faults), 0.0))
    return Outcome(metrics=metrics, checks=checks, attempted=steps,
                   failed=0, counters=counters, trace=tracer.reduce(),
                   memory_peak_bytes=peak, model=model, traffic=tr,
                   compiles_in_window=counter.count)


def _readings_only(r: core.Run) -> Outcome:
    """The control or a planted fault: the reference in the program's
    place, no window."""
    model, tr, opt = r.model, r.traffic, r.model["optimizer"]
    rows, seq = int(tr["batch"]), int(tr["seq_len"])
    ref = reference_readings(model, opt, r.seed, rows, seq, None,
                             regrow_random=r.fault == "regrow_random")
    if r.control:
        readings = reference_readings(model, opt, r.seed, rows, seq, "fp8")
    elif r.fault == "half":
        readings = reference_readings(model, opt, r.seed, rows, seq, None,
                                      keep=rows // 2)
    else:                           # regrow_random: the same pass, other masks
        readings = ref[:3] + (ref[4],)
    checks = _checks(model, tr, readings, ref[:4], r.seed)
    peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
    return Outcome(metrics={}, checks=checks, attempted=0, failed=0,
                   memory_peak_bytes=peak, model=model, traffic=tr)


def gaps(prog_losses, prog_first, prog_change, ref):
    """Worst relative gaps of (losses, first gradient norms, changes)."""
    r_losses, r_first, r_change = ref
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(prog_losses, r_losses))
    med_g = float(np.median(list(r_first.values())))
    g_gap = max(abs(prog_first[k] - r_first[k]) / max(r_first[k], med_g)
                for k in r_first)
    # leaves whose reference gradient is nought to rounding move under Adam
    # by round-off alone: left out of the change
    moved = [k for k in r_change if r_first[k] >= 1e-3 * med_g]
    med_c = float(np.median([r_change[k] for k in moved]))
    c_gap = max(abs(prog_change[k] - r_change[k]) / max(r_change[k], med_c)
                for k in moved)
    return loss_gap, g_gap, c_gap


@jax.jit
def _update_counts(old, new, ref):
    """Per matrix: connections the update dropped, those the reference
    dropped, and those on which the two new masks disagree."""
    ax = (-2, -1)
    return (jnp.sum(old & ~new, axis=ax), jnp.sum(old & ~ref, axis=ax),
            jnp.sum(new ^ ref, axis=ax))


def update_gaps(old: dict, new: dict, ref: dict) -> tuple[float, float]:
    """(flip gap, mismatch), each the worst matrix of any mask (each index
    of a stack's leading dims is one): the gap between the counts of
    connections dropped, over the reference's count; and the connections
    on which the new masks disagree, over twice the reference's count (1
    where the update left the masks as they were)."""
    flip = mismatch = 0.0
    old, new = dict(reference.leaves(old)), dict(reference.leaves(new))
    for name, r in reference.leaves(ref):
        dn, dr, dis = (np.asarray(a, np.float64)
                       for a in _update_counts(old[name], new[name], r))
        dr = np.maximum(dr, 1.0)
        flip = max(flip, float(np.max(np.abs(dn - dr) / dr)))
        mismatch = max(mismatch, float(np.max(dis / (2.0 * dr))))
    return flip, mismatch


def reference_readings(model, opt, seed, rows, seq, quant, keep=None,
                       regrow_random=False):
    """The reference's (losses, first gradient norms, changes, masks after
    the topology update) over the checked steps' batches and the fourth,
    whose end the update follows; ``keep`` rows of each batch alone plants
    the fault of a batch half left out. With ``regrow_random`` a fifth item
    holds the masks of an update that regrows at random."""
    feed = Feed(seed, rows, seq, model["vocab_size"])
    batches = [next(feed) for _ in range(CHECKED_STEPS + 1)]
    batches = [(b["tokens"][:keep], b["targets"][:keep]) for b in batches]
    params, masks = W.make(model, model["param_dtype"], seed)
    change = {}

    def observe(c, p):
        if c == CHECKED_STEPS:
            p0, _ = W.make(model, model["param_dtype"], seed)
            change.update(_change_norms(reference.nest(p), p0))

    losses, first, p4 = reference.train_steps(
        model, opt, params, masks, batches, float(opt["lr"]), quant,
        observe=observe)
    del params
    p4 = reference.nest(p4)
    grads = reference.dense_grads(model, p4, masks, batches[-1], quant)
    step = int(model["sparsity"]["delta_t"])
    new = reference.dst_masks(model, p4, grads, masks, step)
    rand = (reference.dst_masks(model, p4, grads, masks, step,
                                jax.random.PRNGKey(seed % 2**31))
            if regrow_random else None)
    return losses[:CHECKED_STEPS], first, change, new, rand


def _checks(model, tr, readings, ref, seed):
    lim = tr["check"]
    old = W.make(model, model["param_dtype"], seed)[1]
    loss_gap, g_gap, c_gap = gaps(*readings[:3], ref[:3])
    flip, mismatch = update_gaps(old, readings[3], ref[3])
    log(f"[check] losses program {readings[0]} reference {ref[0]}")
    return [Check("loss_gap", loss_gap, float(lim["loss_gap"])),
            Check("grad_norm_gap", g_gap, float(lim["grad_norm_gap"])),
            Check("change_norm_gap", c_gap, float(lim["change_norm_gap"])),
            Check("dst_flip_gap", flip, float(lim["dst_flip_gap"])),
            Check("dst_mask_mismatch", mismatch,
                  float(lim["dst_mask_mismatch"]))]
