"""The serving and training kernels compile for a TPU v5e at qwen3-1.7b widths.

Each test lowers one Pallas kernel with ``interpret=False`` against a v5e
chip that is described, not attached (``jax.experimental.topologies``), and
asserts the compiled program holds the Mosaic kernel (``tpu_custom_call``).
Nothing runs: these catch what the TPU compiler refuses and interpret mode
accepts (unaligned slices, unsupported gathers, VMEM overflow).

The topology is described inside a module fixture, never at import: only
one process may load the TPU library at a time, so every worker must
collect the same tests and only the one running this file loads it.
"""
import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import configs
from repro.core import distributions as D
from repro.kernels import condensed_matmul as cm
from repro.kernels import structured_matmul as sm
from repro.models import attention as A
from repro.sparse import registry as REG

# qwen3-1.7b's sparse stacks (configs/qwen3_1_7b.py) as (d_in, d_out, k):
# published widths, fan-ins from its ERK allocation of 90% SRigL sparsity
STACKS = {s.name: (s.d_in, s.d_out, D.fan_in_from_density(s.d_in, s.density))
          for s in REG.build_registry(configs.get_config("qwen3-1.7b"))}
D_MODEL, D_FF, K_UP = STACKS["blocks/w_up"]
BATCHES = (8, 256)               # decode-specialized variant and tiled grid
# the train-dst cell's attention as (batch, T, q heads, kv heads, head
# size): 8 x 2048 tokens, 16 q heads over 8 kv heads of 128
# (bench/configs/qwen3-1.7b-train-5l.json)
ATTN_CELL = (8, 2048, 16, 8, 128)


@pytest.fixture(scope="module")
def chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip cannot be read back from the
    # persistent cache without one; keep it out of the cache entirely
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)


def _spec(chip, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=chip)


def _compile_text(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


_INSTR = re.compile(r"\s*(?:ROOT )?%\S+ = \w+\[([\d,]*)\]\S* ([\w-]+)\(")


# ops on a weight-shaped array that do not rewrite it: views (bitcast), the
# kernel call, and the asynchronous copies XLA's memory-space assignment
# uses to prefetch an operand into VMEM in its own layout
_NOT_REWRITES = ("parameter", "bitcast", "custom-call", "copy-start",
                 "copy-done")


def _weight_rewrites(text: str, n: int, k: int) -> list[str]:
    """Instructions that write an (n, k) or (k, n) array: a relayout, cast
    or padding pass over the weights before the kernel."""
    dims = {f"{n},{k}", f"{k},{n}"}
    return [line.strip()[:120] for line in text.splitlines()
            if (m := _INSTR.match(line)) and m.group(1) in dims
            and m.group(2) not in _NOT_REWRITES]


@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32])
@pytest.mark.parametrize("stack", ["blocks/wo", "blocks/w_up",
                                   "blocks/w_down"])
def test_condensed_forward_compiles(chip, b, dtype, stack):
    d_in, n_out, k = STACKS[stack]
    text = _compile_text(
        lambda x, v, i: cm.condensed_matmul(x, v, i, interpret=False),
        _spec(chip, (b, d_in), dtype), _spec(chip, (n_out, k), dtype),
        _spec(chip, (n_out, k), jnp.int32))
    # the kernel reads the stored arrays: no relayout or cast pass over them
    assert _weight_rewrites(text, n_out, k) == []


@pytest.mark.parametrize("b", BATCHES)
def test_condensed_f32_storage_forward_compiles(chip, b):
    # f32 values under bf16 activations: the serving default, where the
    # kernel rounds each staged values chunk to bf16 in VMEM
    text = _compile_text(
        lambda x, v, i: cm.condensed_matmul(x, v, i, interpret=False),
        _spec(chip, (b, D_MODEL), jnp.bfloat16),
        _spec(chip, (D_FF, K_UP), jnp.float32),
        _spec(chip, (D_FF, K_UP), jnp.int32))
    assert _weight_rewrites(text, D_FF, K_UP) == []


@pytest.mark.parametrize("b", BATCHES)
def test_condensed_int8_forward_compiles(chip, b):
    text = _compile_text(
        lambda x, v, i, s: cm.condensed_matmul(x, v, i, scales=s,
                                               interpret=False),
        _spec(chip, (b, D_MODEL), jnp.bfloat16),
        _spec(chip, (D_FF, K_UP), jnp.int8),
        _spec(chip, (D_FF, K_UP), jnp.int32), _spec(chip, (D_FF,), jnp.float32))
    assert _weight_rewrites(text, D_FF, K_UP) == []


@pytest.mark.parametrize("b", BATCHES)
def test_condensed_dw_compiles(chip, b):
    text = _compile_text(
        lambda dy, x, i: cm.condensed_matmul_dw(dy, x, i, interpret=False),
        _spec(chip, (b, D_FF), jnp.bfloat16),
        _spec(chip, (b, D_MODEL), jnp.bfloat16),
        _spec(chip, (D_FF, K_UP), jnp.int32))
    # indices in and dw out in the stored layout, not transposed by XLA
    assert _weight_rewrites(text, D_FF, K_UP) == []


@pytest.mark.parametrize("b", BATCHES)
@pytest.mark.parametrize("scaled", [False, True])
def test_condensed_over_active_compiles(chip, b, scaled):
    a = D_FF * 3 // 4                    # a quarter of the neurons ablated
    vdt = jnp.int8 if scaled else jnp.bfloat16
    args = [_spec(chip, (b, D_MODEL), jnp.bfloat16),
            _spec(chip, (a, K_UP), vdt), _spec(chip, (a, K_UP), jnp.int32),
            _spec(chip, (a,), jnp.int32)]
    if scaled:
        args.append(_spec(chip, (a,), jnp.float32))
    text = _compile_text(
        lambda x, v, i, oi, *s: sm.condensed_over_active_matmul(
            x, v, i, oi, D_FF, scales=s[0] if s else None, interpret=False),
        *args)
    assert _weight_rewrites(text, a, K_UP) == []


@pytest.mark.parametrize("b", BATCHES)
def test_structured_compiles(chip, b):
    a = sm.padded_active_count(D_FF * 3 // 4, D_FF)
    _compile_text(
        lambda x, w, ai: sm.structured_matmul(x, w, ai, interpret=False),
        _spec(chip, (b, D_MODEL), jnp.bfloat16),
        _spec(chip, (D_MODEL, D_FF), jnp.bfloat16),
        _spec(chip, (a,), jnp.int32))


@pytest.mark.parametrize("phase,shape,dtype", [
    ("forward", ATTN_CELL, jnp.bfloat16),
    ("grad", ATTN_CELL, jnp.bfloat16),
    # f32 heads of 256: the largest tiles the block rule lets through
    ("grad", (2, 2048, 8, 8, 256), jnp.float32)],
    ids=["cell_forward", "cell_grad", "f32_d256_grad"])
def test_flash_attention_compiles(chip, phase, shape, dtype):
    b, t, h, hkv, d = shape

    @jax.named_scope("attention")
    def attend(q, k, v):
        return A.flash_attention(q, k, v)

    fn = attend if phase == "forward" else jax.grad(
        lambda q, k, v: jnp.sum(attend(q, k, v).astype(jnp.float32)),
        argnums=(0, 1, 2))
    kv = _spec(chip, (b, t, hkv, d), dtype)
    text = _compile_text(fn, _spec(chip, (b, t, h, d), dtype), kv, kv)
    # the backward is the kernel's own dq and dkv kernels, and every kernel
    # keeps the caller's scope in its op_name
    kernels = re.findall(r'op_name="([^"]*/(splash_mha_\w+?))/pallas_call"',
                         text)
    want = ({"splash_mha_fwd_no_residuals"} if phase == "forward" else
            {"splash_mha_fwd_residuals", "splash_mha_dq_no_residuals",
             "splash_mha_dkv_no_residuals"})
    assert {name for _, name in kernels} == want
    assert all("attention" in path.split("/")[1] for path, _ in kernels)
