"""Training step + host-side Trainer loop.

``train_step`` is a single jit-able function closed over (cfg, registry):

  1. forward/backward — sparse layers use straight-through masking, so the
     gradient pytree is DENSE (RigL/SRigL grow criterion) at zero extra cost;
  2. optimizer update — gradients/moments re-masked inside the optimizer;
  3. every ``delta_t`` steps (lax.cond — topology work costs nothing on other
     steps) the DST update prunes/grows/ablates and zeroes newly-grown weights
     (RigL semantics: regrown connections start at w=0, zero momentum).

The Trainer adds the production shell: prefetching, checkpoint/restart,
step-time watchdog (straggler detection), and failure-recovery restore.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.schedule import DSTSchedule
from repro.models import model as M
from repro.optim import make_optimizer
from repro.sparse import registry as REG
from repro.train.state import TrainState, init_train_state

# host spans: written into a running profiler's trace, next to the device's
# operations; without a profiler each costs about a microsecond
_Span = jax.profiler.TraceAnnotation
_Step = jax.profiler.StepTraceAnnotation


def _global_norm(tree) -> jax.Array:
    return jnp.sqrt(sum(jnp.sum(jnp.square(x.astype(jnp.float32)))
                        for x in jax.tree.leaves(tree)) + 1e-30)


def _dst_schedule(cfg) -> DSTSchedule:
    sp = cfg.sparsity
    return DSTSchedule(delta_t=sp.delta_t, alpha=sp.alpha,
                       t_end_fraction=sp.t_end_fraction,
                       total_steps=getattr(cfg, "total_steps", 100_000))


def make_train_step(cfg, registry, lr_fn: Callable, *, clip_norm: float = 1.0,
                    microbatches: int = 1):
    """Build the jit-able HOT-PATH step(state, batch) -> (state, metrics).

    The topology update is deliberately NOT in this program — it runs as its
    own jitted program every delta_t steps (make_dst_step). Keeping the
    selection sorts out of the hot path removes their buffers from this
    program's peak memory and their FLOPs from its roofline; the update cost
    is amortized 1/delta_t (paper App. G makes the same accounting).
    The step DOES accumulate the dense saliency gradients when the config
    asks for a multi-step saliency window (paper D.2 averages 8 steps).
    """
    sched = _dst_schedule(cfg)
    _, opt_update = make_optimizer(cfg.optimizer)
    accum_n = cfg.sparsity.grad_accum_for_saliency

    def _value_and_grad(params, masks, batch):
        return jax.value_and_grad(
            lambda p: M.loss_fn(cfg, p, masks, batch), has_aux=True)(params)

    def train_step(state: TrainState, batch: dict):
        rng, rng_next = jax.random.split(state.rng)
        if microbatches > 1:
            # gradient accumulation: scan over microbatches so activation
            # memory scales with batch/microbatches (how the 1T-param config
            # fits tighter HBM); grads averaged in f32.
            def split(x):
                b = x.shape[0] if x.ndim >= 1 else 1
                return x.reshape(microbatches, b // microbatches, *x.shape[1:])
            mb = {k: (jnp.moveaxis(split(v), 0, 0) if k != "mrope_positions"
                      else v.reshape(3, microbatches, -1, v.shape[-1]).swapaxes(0, 1))
                  for k, v in batch.items()}

            def acc_step(carry, xs):
                (l_sum, g_sum) = carry
                (l, m_), g = _value_and_grad(state.params, state.masks, xs)
                g_sum = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32) / microbatches,
                    g_sum, g)
                return (l_sum + l / microbatches, g_sum), m_

            g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32),
                              state.params)
            (loss, grads), ms = jax.lax.scan(acc_step, (jnp.zeros(()), g0), mb)
            metrics = jax.tree.map(lambda x: x[-1], ms)
            metrics["loss"] = loss
        else:
            (loss, metrics), grads = _value_and_grad(state.params, state.masks,
                                                     batch)

        with jax.named_scope("optimizer"):
            gnorm = _global_norm(grads)
            scale = jnp.minimum(1.0, clip_norm / (gnorm + 1e-9)) if clip_norm else 1.0
            # clip in the gradient's own dtype: a persistent f32 copy of a bf16
            # grad tree would double gradient memory (16 GB/device at 1T params);
            # optimizers upcast per-leaf internally.
            grads_c = jax.tree.map(
                lambda g: (g.astype(jnp.float32) * scale).astype(g.dtype), grads)

            lr = lr_fn(state.step)
            params, opt_state = opt_update(state.params, grads_c, state.opt_state, lr,
                                           masks=state.masks if registry else None)

            # dense-grad window for the saliency criterion (paper D.2): keep the
            # running sum of the last accum_n steps' dense grads per sparse stack.
            grad_accum = state.grad_accum
            if accum_n > 1 and registry:
                decay = jnp.where(state.step % accum_n == 0, 0.0, 1.0)
                new_accum = {}
                for s in registry:
                    a = REG.get_path(grad_accum, s.path)
                    g = REG.get_path(grads, s.path).astype(jnp.float32)
                    REG._set_path(new_accum, s.path, a * decay + g)
                grad_accum = new_accum
        # (accum_n == 1: no persistent accumulator — the topology-update
        # program recomputes its own dense grads, ~1/delta_t amortized cost)

        new_state = TrainState(step=state.step + 1, params=params,
                               opt_state=opt_state, masks=state.masks,
                               neuron_active=state.neuron_active,
                               grad_accum=grad_accum,
                               mask_versions=state.mask_versions, rng=rng_next)
        metrics = dict(metrics)
        metrics.update(grad_norm=gnorm, lr=lr,
                       drop_fraction=sched.drop_fraction(state.step))
        return new_state, metrics

    return train_step


def make_dst_step(cfg, registry, compute_specs: dict | None = None):
    """Build the jit-able topology-update program (runs every delta_t steps).

    state -> state with new masks / neuron_active; newly-grown weights
    restart at 0 (RigL semantics), their optimizer moments are re-masked on
    the next optimizer call.
    """
    sched = _dst_schedule(cfg)
    accum_n = max(cfg.sparsity.grad_accum_for_saliency, 1)

    def dst_step(state: TrainState, batch: dict):
        rng, rng_next = jax.random.split(state.rng)
        drop = sched.drop_fraction(state.step)
        with jax.named_scope("dst_grad"):
            if accum_n > 1:
                sal_grads = jax.tree.map(lambda a: a / accum_n,
                                         state.grad_accum)
            else:
                # recompute dense grads for the grow criterion (1/delta_t
                # amortized)
                grads = jax.grad(
                    lambda p: M.loss_fn(cfg, p, state.masks, batch)[0])(
                        state.params)
                sal_grads = {}
                for s in registry:
                    REG._set_path(
                        sal_grads, s.path,
                        REG.get_path(grads, s.path).astype(jnp.float32))
        sp_state = {"masks": state.masks, "neuron_active": state.neuron_active}
        with jax.named_scope("dst_select"):
            new_sp, _stats = REG.dst_update(cfg, registry, state.params,
                                            sal_grads, sp_state, drop, rng,
                                            compute_specs=compute_specs)
        new_params = jax.tree.map(lambda x: x, state.params)  # fresh containers
        new_versions = dict(state.mask_versions)
        with jax.named_scope("dst_apply"):
            for s in registry:
                w = REG.get_path(new_params, s.path)
                old_m = REG.get_path(state.masks, s.path)
                new_m = REG.get_path(new_sp["masks"], s.path)
                w = jnp.where(new_m & ~old_m, 0.0, w).astype(w.dtype)
                REG._set_path(new_params, s.path, w)
                # stamp the per-stack mask-version counter: the serving
                # plan's incremental refresh re-condenses only stacks whose
                # counter moved
                changed = jnp.any(new_m != old_m)
                new_versions[s.name] = (state.mask_versions[s.name]
                                        + changed.astype(jnp.int32))
        return state._replace(params=new_params, masks=new_sp["masks"],
                              neuron_active=new_sp["neuron_active"],
                              mask_versions=new_versions, rng=rng_next)

    return dst_step


# convenience single-call API used by tests/examples
def train_step(cfg, registry, state, batch, lr: float = 1e-3):
    step_fn = make_train_step(cfg, registry, lambda s: jnp.float32(lr))
    return step_fn(state, batch)


@dataclasses.dataclass
class Trainer:
    """Host-side production loop: prefetch, checkpoint/restart, watchdog."""

    cfg: Any
    lr_fn: Callable
    ckpt_dir: str | None = None
    ckpt_every: int = 1000
    keep_checkpoints: int = 3
    log_every: int = 50
    straggler_factor: float = 3.0   # step slower than 3x median -> flagged
    # live train->serve sync (repro.sync.Publisher): publish right after
    # every DST step (the moment mask_versions move — topology deltas), and
    # additionally every ``publish_every`` steps so serving replicas track
    # the weight VALUES between topology updates (values-only deltas).
    publisher: Any = None
    publish_every: int | None = None

    def __post_init__(self):
        self.registry = REG.build_registry(self.cfg)
        self._step_fn = None
        self._step_times: list[float] = []
        self.straggler_events: list[tuple[int, float]] = []
        # device values ``fit`` turned into Python values: each one waits
        # for the device to finish what was dispatched before it
        self.host_syncs = 0

    def init_or_restore(self, key) -> TrainState:
        from repro.train import checkpoint as CKPT
        if self.ckpt_dir:
            latest = CKPT.latest_step(self.ckpt_dir)
            if latest is not None:
                template = init_train_state(self.cfg, key)
                with _Span("trainer.restore"):
                    return CKPT.restore(self.ckpt_dir, latest, template)
        return init_train_state(self.cfg, key)

    def programs(self):
        """(train step, DST update) as jitted programs, built on first use;
        the DST update is None for a model with no sparse stack."""
        if self._step_fn is None:
            self._step_fn = jax.jit(make_train_step(self.cfg, self.registry, self.lr_fn),
                                    donate_argnums=(0,))
            self._dst_fn = (jax.jit(make_dst_step(self.cfg, self.registry),
                                    donate_argnums=(0,))
                            if self.registry else None)
        return self._step_fn, self._dst_fn

    def _to_host(self, convert: Callable, value):
        self.host_syncs += 1
        return convert(value)

    def fit(self, state: TrainState, batches, n_steps: int,
            log_fn: Callable = print) -> TrainState:
        """Run ``n_steps`` steps. Each is a profiler step ``trainer.step``
        holding host spans ``trainer.<phase>``, on the clock of the device
        trace when a profiler is running."""
        from repro.train import checkpoint as CKPT
        self.programs()
        sched = _dst_schedule(self.cfg)
        it = iter(batches)
        start = self._to_host(int, state.step)
        for i in range(start, start + n_steps):
            with _Step("trainer.step", step_num=i):
                with _Span("trainer.next_batch"):
                    batch = next(it)
                t0 = time.perf_counter()
                try:
                    with _Span("trainer.dispatch_step"):
                        state, metrics = self._step_fn(state, batch)
                    dst_ran = False
                    if self._dst_fn is not None:
                        with _Span("trainer.dst_due"):
                            dst_ran = self._to_host(
                                bool, sched.is_update_step(i + 1))
                    if dst_ran:
                        with _Span("trainer.dispatch_dst"):
                            state = self._dst_fn(state, batch)
                    if self.publisher is not None and (
                            dst_ran or (self.publish_every
                                        and (i + 1) % self.publish_every == 0)):
                        # host-side hook, outside the jitted programs: DST
                        # just stamped mask_versions, so this generation
                        # ships the moved stacks as topology deltas
                        with _Span("trainer.publish"):
                            self.publisher.publish(state)
                except Exception:
                    # fault tolerance: restore from the last checkpoint and
                    # rethrow if no checkpoint exists (caller decides whether
                    # to re-enter).
                    if self.ckpt_dir and CKPT.latest_step(self.ckpt_dir) is not None:
                        log_fn(f"[trainer] step {i}: failure — restoring last checkpoint")
                        with _Span("trainer.restore"):
                            state = CKPT.restore(
                                self.ckpt_dir, CKPT.latest_step(self.ckpt_dir),
                                state)
                        continue
                    raise
                dt = time.perf_counter() - t0
                self._watch_stragglers(i, dt, log_fn)
                if i % self.log_every == 0:
                    with _Span("trainer.log"):
                        loss = self._to_host(float, metrics["loss"])
                        log_fn(f"[trainer] step {i} loss {loss:.4f} ({dt*1e3:.0f} ms)")
                if self.ckpt_dir and (i + 1) % self.ckpt_every == 0:
                    with _Span("trainer.checkpoint"):
                        CKPT.save(self.ckpt_dir, state,
                                  keep=self.keep_checkpoints)
        return state

    def _watch_stragglers(self, step: int, dt: float, log_fn):
        self._step_times.append(dt)
        if len(self._step_times) >= 20:
            med = sorted(self._step_times[-100:])[len(self._step_times[-100:]) // 2]
            if dt > self.straggler_factor * med:
                self.straggler_events.append((step, dt))
                log_fn(f"[trainer] straggler: step {step} took {dt:.2f}s (median {med:.2f}s)")
