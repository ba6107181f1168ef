"""The system under test, as the benchmark reaches it.

Builds the program's configuration from a benchmark configuration file and
checks that the program's parameter layout and fan-ins are those the
benchmark's weights were made for. Everything else the harness takes from
the program goes through the entry points named here.
"""
from __future__ import annotations

import jax


def arch_config(model: dict, *, dtype: str, param_dtype: str,
                n_layers: int | None = None):
    """The program's ``ArchConfig`` for a benchmark configuration."""
    from repro.configs.base import ArchConfig, SparsityConfig
    sp = model["sparsity"]
    return ArchConfig(
        name=model["name"], family="dense",
        n_layers=int(n_layers or model["num_hidden_layers"]),
        d_model=model["hidden_size"], n_heads=model["num_attention_heads"],
        n_kv_heads=model["num_key_value_heads"], head_dim=model["head_dim"],
        d_ff=model["intermediate_size"], vocab_size=model["vocab_size"],
        qk_norm=True, rope_theta=float(model["rope_theta"]),
        tie_embeddings=bool(model["tie_word_embeddings"]),
        norm_eps=float(model["rms_norm_eps"]), dtype=dtype,
        param_dtype=param_dtype,
        sparsity=SparsityConfig(
            method=sp["method"], sparsity=float(sp["sparsity"]),
            distribution=sp["distribution"], gamma_sal=float(sp["gamma_sal"]),
            ablation=bool(sp["ablation"]), delta_t=int(sp["delta_t"]),
            alpha=float(sp["alpha"]),
            t_end_fraction=float(sp["t_end_fraction"])))


def check_layout(cfg, model: dict, params) -> list:
    """The program's registry, after checking that its fan-ins and its
    parameter shapes are those of the benchmark's weights."""
    from repro.models import model as M
    from repro.sparse import registry as REG
    reg = REG.build_registry(cfg)
    fan = REG.k_fan_map(cfg, reg)
    want = {k: int(v) for k, v in model["sparsity"]["fan_in"].items()}
    if fan != want:
        raise RuntimeError(f"program fan-ins {fan} differ from the "
                           f"configuration's {want}")
    shapes = jax.eval_shape(lambda k: M.init_params(cfg, k, fan),
                            jax.random.PRNGKey(0))
    got = jax.tree.map(lambda a: (a.shape, str(a.dtype)), params)
    exp = jax.tree.map(lambda a: (a.shape, str(a.dtype)), shapes)
    if got != exp:
        raise RuntimeError(f"parameter layout differs from the program's: "
                           f"{got} vs {exp}")
    return reg
