"""The fused flash path of ``chunked_attention``: parity and its gate.

``flash_attention`` (the shipped splash kernel, run in interpret mode on
the CPU) against the chunked scan, forward and ``jax.grad`` in q, k and v;
and the gate that picks the path, read through ``path_counts``. The gate's
backend probe is patched to a TPU where a test needs the fused path traced;
tracing (``jax.make_jaxpr``) lowers nothing, so no kernel runs there.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AbstractMesh, AxisType, NamedSharding, PartitionSpec

from repro import configs
from repro.data.pipeline import make_train_batch
from repro.models import attention as A
from repro.models import model as M
from repro.sparse import registry as REG

# (batch, T, q heads, kv heads), blocks from flash_block_sizes: GQA over 3
# blocks of 128, MHA over 3 of 256, MQA over 2 of 1024 (scores 512 keys at
# a time)
SHAPES = [(2, 384, 4, 2), (1, 768, 2, 2), (1, 2048, 2, 1)]

# elementwise |flash - chunked| <= atol + rtol * |chunked|. f32: both sum
# the same f32 terms in another order (observed <= 2.4e-6 at values ~6).
# bf16: the kernel feeds p to the PV product in f32 where the scan rounds it
# to bf16, and each bf16 output rounds once more: two bf16 ulps at the
# values' scale (eps 2**-7).
TOLERANCE = {jnp.float32: dict(atol=2e-5, rtol=2e-5),
             jnp.bfloat16: dict(atol=2 ** -6, rtol=2 ** -6)}


def _inputs(b, t, h, hkv, dtype, d=128):
    kq, kk, kv, kc = jax.random.split(jax.random.PRNGKey(t + h), 4)
    return (jax.random.normal(kq, (b, t, h, d), dtype),
            jax.random.normal(kk, (b, t, hkv, d), dtype),
            jax.random.normal(kv, (b, t, hkv, d), dtype),
            jax.random.normal(kc, (b, t, h, d), jnp.float32))


def _grouped(h, hkv):
    return tuple(i // (h // hkv) for i in range(h))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_flash_matches_chunked_forward_and_grad(shape, dtype):
    b, t, h, hkv = shape
    q, k, v, cot = _inputs(b, t, h, hkv, dtype)
    chunked = lambda q, k, v: A.chunked_attention(  # noqa: E731
        q, k, v, head_to_kv=_grouped(h, hkv))
    flash = lambda q, k, v: A.flash_attention(q, k, v, interpret=True)  # noqa: E731

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v).astype(jnp.float32) * cot)

    outs = [jax.jit(fn)(q, k, v) for fn in (chunked, flash)]
    grads = [jax.jit(jax.grad(loss(fn), argnums=(0, 1, 2)))(q, k, v)
             for fn in (chunked, flash)]
    assert outs[1].shape == (b, t, h, 128) and outs[1].dtype == dtype
    for want, got in zip([outs[0], *grads[0]], [outs[1], *grads[1]]):
        assert got.dtype == want.dtype
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   **TOLERANCE[dtype])


_MULTI_DEVICE = NamedSharding(
    AbstractMesh((1, 4), ("data", "model"), axis_types=(AxisType.Auto,) * 2),
    PartitionSpec())

# (id, on a TPU, q shape, kv shape, head map, keyword arguments, path)
GATE = [
    ("qualifying", True, (1, 256, 4, 128), (1, 256, 2, 128), None, {}, "flash"),
    ("cpu", False, (1, 256, 4, 128), (1, 256, 2, 128), None, {}, "chunked"),
    ("window", True, (1, 256, 4, 128), (1, 256, 2, 128), None,
     {"window": 64}, "chunked"),
    ("q_offset", True, (1, 256, 4, 128), (1, 256, 2, 128), None,
     {"q_offset": 128}, "chunked"),
    ("non_causal", True, (1, 256, 4, 128), (1, 256, 2, 128), None,
     {"causal": False}, "chunked"),
    ("ragged_t", True, (1, 200, 4, 128), (1, 200, 2, 128), None, {},
     "chunked"),
    ("q_shorter_than_kv", True, (1, 128, 4, 128), (1, 256, 2, 128), None,
     {}, "chunked"),
    ("head_dim_64", True, (1, 256, 4, 64), (1, 256, 2, 64), None, {},
     "chunked"),
    ("padded_heads", True, (1, 256, 6, 128), (1, 256, 2, 128),
     (0, 0, 1, 1, 0, 0), {}, "chunked"),
    ("multi_device", True, (1, 256, 4, 128), (1, 256, 2, 128), None,
     {"sharding": _MULTI_DEVICE}, "chunked"),
]


@pytest.mark.parametrize("on_tpu,q_shape,kv_shape,head_to_kv,kw,path",
                         [g[1:] for g in GATE], ids=[g[0] for g in GATE])
def test_gate_picks_path(monkeypatch, on_tpu, q_shape, kv_shape, head_to_kv,
                         kw, path):
    monkeypatch.setattr(A, "_on_tpu", lambda: on_tpu)
    kw = dict(kw)
    sharding = kw.pop("sharding", None)
    head_to_kv = head_to_kv or _grouped(q_shape[2], kv_shape[2])
    specs = [jax.ShapeDtypeStruct(s, jnp.bfloat16, sharding=sharding)
             for s in (q_shape, kv_shape, kv_shape)]
    before = A.path_counts()
    jax.jit(lambda q, k, v: A.chunked_attention(
        q, k, v, head_to_kv=head_to_kv, **kw)).trace(*specs)
    after = A.path_counts()
    other = {"flash": "chunked", "chunked": "flash"}[path]
    assert after[path] == before[path] + 1
    assert after[other] == before[other]


@pytest.mark.parametrize("on_tpu,head_dim,path", [
    (True, 128, "flash"), (False, 128, "chunked"), (True, 16, "chunked")],
    ids=["tpu_d128", "cpu_d128", "tpu_smoke_d16"])
def test_train_step_attention_path(monkeypatch, on_tpu, head_dim, path):
    # the loss and its gradient, as the train step traces them; the smoke
    # config's head size (16) keeps every CPU test on the chunked scan
    monkeypatch.setattr(A, "_on_tpu", lambda: on_tpu)
    cfg = configs.get_smoke_config("qwen3-1.7b").replace(head_dim=head_dim)
    reg = REG.build_registry(cfg)
    key = jax.random.PRNGKey(0)
    params = M.init_params(cfg, key, REG.k_fan_map(cfg, reg))
    masks = REG.init_sparsity_state(cfg, key, reg)["masks"]
    batch = make_train_batch(cfg, jax.random.PRNGKey(1), 2, 128)
    before = A.path_counts()
    jax.make_jaxpr(jax.grad(
        lambda p: M.loss_fn(cfg, p, masks, batch)[0]))(params)
    after = A.path_counts()
    other = {"flash": "chunked", "chunked": "flash"}[path]
    assert after[path] > before[path]
    assert after[other] == before[other]
