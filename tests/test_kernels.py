"""Pallas condensed-matmul kernels vs the pure-jnp oracle (shape/dtype sweep)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_compat import given, settings, st
from _numerics import assert_sums_close

from repro.core import topology
from repro.kernels import condensed_matmul as cm
from repro.kernels import ops, ref


SHAPES = [
    (1, 64, 32, 8),        # online inference (paper Fig. 4a)
    (4, 64, 32, 8),
    (130, 300, 257, 5),    # non-aligned everything
    (256, 3072, 768, 307), # the paper's ViT-B/16 benchmark layer @ 90%
    (8, 128, 128, 1),      # k=1 edge
    (3, 16, 8, 16),        # k == d_in (dense-equivalent)
]


@pytest.mark.parametrize("b,d_in,n_out,k", SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_condensed_matmul_sweep(b, d_in, n_out, k, dtype):
    key = jax.random.PRNGKey(b * 7 + k)
    kx, kw, ki = jax.random.split(key, 3)
    x = jax.random.normal(kx, (b, d_in), jnp.float32).astype(dtype)
    w = jax.random.normal(kw, (n_out, k), jnp.float32).astype(dtype)
    idx = jax.random.randint(ki, (n_out, k), 0, d_in)
    y = ops.condensed_linear(x, w, idx)
    # oracle in f32 (the kernel accumulates f32 regardless of input dtype, so
    # a bf16-accumulated oracle would be the LESS accurate side at large k)
    y_ref = ref.condensed_matmul_ref(x.astype(jnp.float32), w.astype(jnp.float32), idx)
    if dtype == jnp.float32:
        # the kernel adds the k products slot by slot, the oracle in XLA's
        # tree order: equal within the reordering bound of k-term sums
        abs_sum = ref.condensed_matmul_ref(jnp.abs(x), jnp.abs(w), idx)
        assert_sums_close(y, y_ref, abs_sum, k)
    else:  # bf16 inputs: elementwise products rounded to bf16 before f32 sum
        atol = 0.05 * np.sqrt(k)
        np.testing.assert_allclose(np.array(y, np.float32), np.array(y_ref),
                                   rtol=3e-2, atol=atol)


def test_condensed_matmul_grads_match_oracle():
    key = jax.random.PRNGKey(0)
    b, d_in, n_out, k = 16, 96, 48, 12
    x = jax.random.normal(key, (b, d_in))
    w = jax.random.normal(jax.random.fold_in(key, 1), (n_out, k))
    idx = jax.random.randint(jax.random.fold_in(key, 2), (n_out, k), 0, d_in)
    f = lambda x, w: jnp.sum(jnp.tanh(ops.condensed_linear(x, w, idx)))
    g = lambda x, w: jnp.sum(jnp.tanh(ref.condensed_matmul_ref(x, w, idx)))
    gx1, gw1 = jax.grad(f, (0, 1))(x, w)
    gx2, gw2 = jax.grad(g, (0, 1))(x, w)
    np.testing.assert_allclose(np.array(gx1), np.array(gx2), atol=1e-5)
    np.testing.assert_allclose(np.array(gw1), np.array(gw2), atol=1e-5)


def test_onehot_formulation_equivalent():
    key = jax.random.PRNGKey(3)
    x = jax.random.normal(key, (8, 64))
    w = jax.random.normal(jax.random.fold_in(key, 1), (32, 6))
    # distinct indices per row for exact one-hot equivalence
    idx = jnp.stack([jax.random.permutation(jax.random.fold_in(key, i), 64)[:6]
                     for i in range(32)])
    np.testing.assert_allclose(
        np.array(ref.onehot_matmul_ref(x, w, idx)),
        np.array(ref.condensed_matmul_ref(x, w, idx)), atol=1e-4)


@given(st.integers(0, 1000))
@settings(max_examples=10, deadline=None)
def test_condensed_equals_masked_dense_property(seed):
    """The paper's core identity: condensed(x) == x @ (mask * W)."""
    key = jax.random.PRNGKey(seed)
    d_in, n_out, k = 48, 24, 7
    mask = topology.random_constant_fan_in_mask(key, d_in, n_out, k)
    w_dense = jax.random.normal(jax.random.fold_in(key, 1), (d_in, n_out)) * mask
    x = jax.random.normal(jax.random.fold_in(key, 2), (5, d_in))
    vals, idx = topology.dense_to_condensed(w_dense, mask, k)
    y_cond = ops.condensed_linear(x, vals, idx)
    y_dense = x @ w_dense
    np.testing.assert_allclose(np.array(y_cond), np.array(y_dense), atol=1e-5)


def test_structured_dense_path():
    """Fig. 4 'structured' representation: ablated neurons dropped exactly."""
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (32, 16))
    active = jnp.arange(16) % 3 != 0
    x = jax.random.normal(jax.random.fold_in(key, 1), (4, 32))
    y = ops.structured_dense(x, w, active)
    assert np.allclose(np.array(y[:, ~np.array(active)]), 0.0)
    np.testing.assert_allclose(np.array(y[:, np.array(active)]),
                               np.array((x @ w)[:, np.array(active)]), atol=1e-5)


def test_blockspec_padding_paths():
    """Shapes straddling block boundaries exercise the pallas padding logic."""
    for b, n in [(127, 129), (128, 128), (129, 127), (1, 1)]:
        x = jnp.ones((b, 32))
        w = jnp.ones((n, 4))
        idx = jnp.zeros((n, 4), jnp.int32)
        y = cm.condensed_matmul(x, w, idx, block_b=128, block_n=128, interpret=True)
        assert y.shape == (b, n)
        np.testing.assert_allclose(np.array(y), 4.0)


# ---------------------------------------------------------------------------
# hardened edge/property coverage: dw kernel, non-aligned blocks, bf16 accum,
# duplicate indices
# ---------------------------------------------------------------------------

DW_SHAPES = [
    (130, 300, 257, 5),   # b % block_b != 0 AND n_out % block_n != 0
    (7, 64, 129, 3),      # n_out just past one block
    (128, 96, 128, 1),    # k=1, exactly aligned
    (1, 32, 1, 4),        # single output neuron, single example
]


@pytest.mark.parametrize("b,d_in,n_out,k", DW_SHAPES)
def test_condensed_dw_kernel_vs_oracle(b, d_in, n_out, k):
    key = jax.random.PRNGKey(b * 13 + n_out)
    dy = jax.random.normal(key, (b, n_out))
    x = jax.random.normal(jax.random.fold_in(key, 1), (b, d_in))
    idx = jax.random.randint(jax.random.fold_in(key, 2), (n_out, k), 0, d_in)
    dw = cm.condensed_matmul_dw(dy, x, idx, block_n=128, interpret=True)
    dw_ref = ref.condensed_matmul_dw_ref(dy, x, idx)
    assert dw.shape == (n_out, k)
    np.testing.assert_allclose(np.array(dw), np.array(dw_ref), rtol=1e-5,
                               atol=1e-5)


def test_condensed_dw_bf16_accumulates_f32():
    """bf16 dy/x: gradient comes back f32 (values_dtype) and is close to the
    f32 oracle — the kernel upcasts before the batch reduction, so the error
    is one bf16 rounding per operand, not O(sqrt(B)) accumulation drift."""
    b, d_in, n_out, k = 512, 64, 32, 8
    key = jax.random.PRNGKey(0)
    dy = jax.random.normal(key, (b, n_out)).astype(jnp.bfloat16)
    x = jax.random.normal(jax.random.fold_in(key, 1), (b, d_in)).astype(jnp.bfloat16)
    idx = jax.random.randint(jax.random.fold_in(key, 2), (n_out, k), 0, d_in)
    dw = cm.condensed_matmul_dw(dy, x, idx, interpret=True)
    assert dw.dtype == jnp.float32
    dw_ref = ref.condensed_matmul_dw_ref(dy.astype(jnp.float32),
                                         x.astype(jnp.float32), idx)
    # inputs rounded to bf16 once; the f32-accumulated result stays within a
    # few bf16 ulps of the f32 oracle even at B=512
    np.testing.assert_allclose(np.array(dw), np.array(dw_ref), rtol=3e-2,
                               atol=0.15 * np.sqrt(b) / 8)


def test_condensed_fwd_duplicate_indices():
    """Duplicate indices within a neuron are summed, matching the oracle and
    the scatter-based one-hot formulation (a neuron may reference the same
    input feature twice after export padding)."""
    x = jnp.arange(1, 13, dtype=jnp.float32).reshape(3, 4)
    w = jnp.array([[2.0, 3.0, 0.5], [1.0, 1.0, 1.0]])
    idx = jnp.array([[1, 1, 3], [0, 0, 0]])  # heavy duplication
    y = ops.condensed_linear(x, w, idx)
    y_ref = ref.condensed_matmul_ref(x, w, idx)
    y_onehot = ref.onehot_matmul_ref(x, w, idx)
    np.testing.assert_allclose(np.array(y), np.array(y_ref), atol=1e-6)
    np.testing.assert_allclose(np.array(y_ref), np.array(y_onehot), atol=1e-6)
    # hand-check one entry: neuron 0, example 0: 2*x[1] + 3*x[1] + 0.5*x[3]
    assert float(y[0, 0]) == pytest.approx(2 * 2 + 3 * 2 + 0.5 * 4)


def test_condensed_dw_duplicate_indices():
    """dw gathers (never scatters), so duplicate indices each get their own
    gradient entry: dw[n, j] = sum_b dy[b, n] * x[b, idx[n, j]] independently."""
    key = jax.random.PRNGKey(4)
    dy = jax.random.normal(key, (6, 2))
    x = jax.random.normal(jax.random.fold_in(key, 1), (6, 5))
    idx = jnp.array([[2, 2, 2], [0, 4, 4]])
    dw = cm.condensed_matmul_dw(dy, x, idx, interpret=True)
    np.testing.assert_allclose(np.array(dw),
                               np.array(ref.condensed_matmul_dw_ref(dy, x, idx)),
                               atol=1e-5)
    # duplicated columns carry identical gradients
    np.testing.assert_allclose(np.array(dw[0, 0]), np.array(dw[0, 1]), atol=1e-6)


@given(st.integers(0, 10_000), st.integers(1, 3), st.integers(1, 3))
@settings(max_examples=15, deadline=None)
def test_condensed_fwd_dw_property_nonaligned(seed, b_off, n_off):
    """fwd and dw match the oracle for shapes straddling block boundaries in
    both grid dimensions simultaneously (block_b=block_n=32 here to keep the
    interpret-mode sweep fast while still crossing block edges)."""
    key = jax.random.PRNGKey(seed)
    b, d_in, n_out, k = 32 + b_off, 40, 32 + n_off, 4
    x = jax.random.normal(key, (b, d_in))
    w = jax.random.normal(jax.random.fold_in(key, 1), (n_out, k))
    idx = jax.random.randint(jax.random.fold_in(key, 2), (n_out, k), 0, d_in)
    y = cm.condensed_matmul(x, w, idx, block_b=32, block_n=32, interpret=True)
    np.testing.assert_allclose(np.array(y),
                               np.array(ref.condensed_matmul_ref(x, w, idx)),
                               rtol=1e-5, atol=1e-5)
    dy = jax.random.normal(jax.random.fold_in(key, 3), (b, n_out))
    dw = cm.condensed_matmul_dw(dy, x, idx, block_n=32, interpret=True)
    np.testing.assert_allclose(np.array(dw),
                               np.array(ref.condensed_matmul_dw_ref(dy, x, idx)),
                               rtol=1e-5, atol=1e-5)


def test_condensed_linear_nd_leading_dims():
    """Rank-polymorphic wrapper: (B, T, d) and (d,) inputs agree with the 2-D
    kernel — the decode path calls it on (B, 1, d) activations."""
    key = jax.random.PRNGKey(2)
    d_in, n_out, k = 24, 16, 5
    w = jax.random.normal(key, (n_out, k))
    idx = jax.random.randint(jax.random.fold_in(key, 1), (n_out, k), 0, d_in)
    x = jax.random.normal(jax.random.fold_in(key, 2), (3, 7, d_in))
    y = ops.condensed_linear_nd(x, w, idx)
    assert y.shape == (3, 7, n_out)
    y2 = ops.condensed_linear(x.reshape(-1, d_in), w, idx).reshape(3, 7, n_out)
    np.testing.assert_allclose(np.array(y), np.array(y2), atol=1e-6)
