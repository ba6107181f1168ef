"""Tolerances for comparing two float sums of the same terms.

XLA is free to reorder a reduction (a dot, a ``jnp.sum``), and a Pallas
kernel sums in its own order, so two programs computing the same sum agree
only up to rounding. Each ordering of an n-term f32 sum is within
(n - 1) * eps * sum(|terms|) of the exact value (Higham, *Accuracy and
Stability of Numerical Algorithms*, 2nd ed., eq. 4.4), so two orderings are
within twice that of each other; an output stored in a narrower dtype adds
at most one rounding of that dtype. Exact zeros are not sums: outputs that
a kernel writes as zeros (ablated neurons, padding) are compared bitwise.
"""
import ml_dtypes
import numpy as np

F32_EPS = float(np.finfo(np.float32).eps)


def assert_sums_close(actual, expected, abs_sum, n_terms: int):
    """``actual`` and ``expected`` are two orderings of sums whose
    absolute terms add up to ``abs_sum`` (elementwise), ``n_terms`` each."""
    actual = np.asarray(actual)
    expected = np.asarray(expected)
    out_eps = (0.0 if actual.dtype == np.float32
               else float(ml_dtypes.finfo(actual.dtype).eps))
    a = actual.astype(np.float64)
    e = expected.astype(np.float64)
    tol = (2 * max(n_terms - 1, 1) * F32_EPS * np.asarray(abs_sum, np.float64)
           + out_eps * np.abs(e))
    bad = np.abs(a - e) > tol
    assert not bad.any(), (
        f"{int(bad.sum())} of {bad.size} sums differ by more than their "
        f"reordering bound; worst excess {float(np.max(np.abs(a - e) - tol)):.3g}")


def assert_zeros_where(actual, zero):
    """Outputs marked ``zero`` are exact zeros (bitwise, no tolerance)."""
    actual = np.asarray(actual)
    zero = np.broadcast_to(np.asarray(zero, bool), actual.shape)
    assert np.all(actual[zero] == 0), "an ablated or padded output is not 0"
