"""A cell's training run traced with the CPU profiler, where each executor
thread plays a device: what the tests of the scope attribution and of the
per-layer readers read."""
import glob
import os

import jax
import jax.numpy as jnp

from harness import program, train
from harness import weights as W


def read(trace_dir):
    """(devices, modules, spans, runs) of a CPU profile: each executor
    thread's operations (events with an ``hlo_op`` stat), each program run
    on that thread as a module execution (from its first operation to its
    last), every ``trainer.*`` span, and the start of each program run by
    (module, run id)."""
    from jax.profiler import ProfileData
    path, = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    devices, modules, spans, runs = [], [], [], {}
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:CPU"):
            continue
        for line in plane.lines:
            ops, mods = [], {}
            for e in line.events:
                s, t = int(e.start_ns), int(e.start_ns + e.duration_ns)
                if e.name.startswith("trainer."):
                    spans.append((e.name, s, t))
                stats = dict(e.stats)
                if "hlo_op" not in stats:
                    continue
                ops.append((e.name, s, t))
                key = (stats["hlo_module"], stats.get("run_id"))
                m = mods.get(key, (key[0], s, t))
                mods[key] = (key[0], min(m[1], s), max(m[2], t))
                runs[key] = min(runs.get(key, s), s)
            if ops:
                devices.append(ops)
                modules.append(list(mods.values()))
    return devices, modules, spans, runs


def traced_fit(model, traffic, seed, trace_dir):
    """Two ``fit`` steps, the second ending in the DST update, under the
    CPU profiler, after two that compile both programs; ``read`` of the
    trace."""
    from repro.train.trainer import Trainer
    rows, seq = traffic["batch"], traffic["seq_len"]
    cfg = program.arch_config(model, dtype=model["compute_dtype"],
                              param_dtype=model["param_dtype"])
    params, masks = W.make(model, model["param_dtype"], seed)
    reg = program.check_layout(cfg, model, params, masks)
    delta_t = int(model["sparsity"]["delta_t"])
    state = train._state(cfg, reg, params, masks, delta_t - 3,
                         jax.random.PRNGKey(3))
    lr = float(model["optimizer"]["lr"])
    trainer = Trainer(cfg=cfg, lr_fn=lambda s: jnp.float32(lr), log_every=1)
    feed = train.Feed(seed, rows, seq, model["vocab_size"])
    quiet = lambda msg: None
    state = trainer.fit(state, feed, 2, log_fn=quiet)    # compiles both
    jax.profiler.start_trace(trace_dir)
    state = trainer.fit(state, feed, 2, log_fn=quiet)
    jax.block_until_ready(state.params)
    jax.profiler.stop_trace()
    return read(trace_dir)
