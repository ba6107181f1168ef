"""The system under test, as the benchmark reaches it.

Builds the program's configuration from a benchmark configuration file
through its architecture module (``harness.archs``), checks that the
program's parameter layout and fan-ins are those the benchmark's weights
were made for, and reads the ``Trainer``'s counters. Everything else the
harness takes from the program goes through the entry points named here.
"""
from __future__ import annotations

import jax

from harness import archs
from harness import weights as W
from harness.reference import leaves

# the Trainer's counters every run hands on; an architecture module's
# COUNTERS adds its own
COUNTERS = ("host_syncs", "straggler_events")


def arch_config(model: dict, *, dtype: str, param_dtype: str):
    """The program's ``ArchConfig`` for a benchmark configuration."""
    return archs.of(model).arch_config(model, dtype=dtype,
                                       param_dtype=param_dtype)


def sparsity_config(model: dict):
    """The program's ``SparsityConfig`` from the configuration's
    ``sparsity``."""
    from repro.configs.base import SparsityConfig
    sp = model["sparsity"]
    return SparsityConfig(
        method=sp["method"], sparsity=float(sp["sparsity"]),
        distribution=sp["distribution"], gamma_sal=float(sp["gamma_sal"]),
        ablation=bool(sp["ablation"]), delta_t=int(sp["delta_t"]),
        alpha=float(sp["alpha"]), t_end_fraction=float(sp["t_end_fraction"]))


def check_layout(cfg, model: dict, params, masks) -> list:
    """The program's registry, after checking that its sparse matrices,
    their fan-ins and its parameter shapes are those of the benchmark's
    weights and masks."""
    from repro.models import model as M
    from repro.sparse import registry as REG
    reg = REG.build_registry(cfg)
    fan = {s.path: REG.k_fan_map(cfg, [s])[s.path[-1]] for s in reg}
    want = W.fan_ins(model)
    if fan != want:
        raise RuntimeError(f"program fan-ins {fan} differ from the "
                           f"configuration's {want}")
    stacks = {s.path: (*s.lead, s.d_in, s.d_out) for s in reg}
    got = {tuple(k.split("/")): tuple(v.shape) for k, v in leaves(masks)}
    if stacks != got:
        raise RuntimeError(f"mask shapes {got} differ from the program's "
                           f"sparse matrices {stacks}")
    shapes = jax.eval_shape(lambda k: M.init_params(cfg, k, REG.k_fan_map(
        cfg, reg)), jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    exp = jax.tree.map(lambda a: (a.shape, str(a.dtype)), shapes)
    if got != exp:
        raise RuntimeError(f"parameter layout differs from the program's: "
                           f"{got} vs {exp}")
    return reg


def counters(trainer, names) -> dict[str, int]:
    """The ``Trainer``'s counters as ``program.<name>``: a number as it
    stands, a list of events by its length."""
    out = {}
    for name in names:
        v = getattr(trainer, name)
        out[f"program.{name}"] = len(v) if isinstance(v, (list, tuple)) else v
    return out
