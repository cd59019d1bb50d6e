"""tanh-GELU satellite (ISSUE 7): the approximate-tanh variant
0.5x(1+tanh(sqrt(2/pi)(x+0.044715x^3))) vs the exact erf form, through
every door (the ``gelu`` op, ``LeakyReLU(act_type='gelu')``,
``gluon.nn.GELU``) and the ``MXNET_GELU_TANH`` default knob.

The knob resolves when an executable is FIRST BUILT for the attr set
(trace time: a program already built keeps what it read) — the knob tests
use fresh shapes so jax traces anew under the flipped environment.
"""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.gluon import nn


def _erf_gelu(x):
    from scipy.special import erf
    return 0.5 * x * (1.0 + erf(x / np.sqrt(2.0)))


def _tanh_gelu(x):
    return 0.5 * x * (1.0 + np.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


X = np.linspace(-6.0, 6.0, 193, dtype=np.float32)


def test_gelu_exact_erf_by_default():
    pytest.importorskip("scipy")
    out = mx.nd.gelu(mx.nd.array(X)).asnumpy()
    np.testing.assert_allclose(out, _erf_gelu(X.astype(np.float64)),
                               rtol=1e-6, atol=1e-6)


def test_gelu_tanh_matches_closed_form_fp32():
    out = mx.nd.gelu(mx.nd.array(X), approximate=True).asnumpy()
    np.testing.assert_allclose(out, _tanh_gelu(X.astype(np.float64)),
                               rtol=1e-5, atol=1e-6)


def test_gelu_tanh_vs_erf_parity_fp32():
    """The approximation's analytic error bound: |tanh-gelu - erf-gelu|
    <= ~1e-3 absolute everywhere (max ~3e-4 near |x|~2) — tight enough
    to swap in as an MFU lever without touching convergence."""
    exact = mx.nd.gelu(mx.nd.array(X), approximate=False).asnumpy()
    approx = mx.nd.gelu(mx.nd.array(X), approximate=True).asnumpy()
    assert np.max(np.abs(exact - approx)) < 1e-3
    assert not np.array_equal(exact, approx)   # genuinely different path


def test_gelu_tanh_vs_erf_parity_bf16():
    """In bf16 the two forms are indistinguishable beyond bf16 epsilon
    (~0.8% relative): the approximation error drowns in the format."""
    import jax.numpy as jnp
    xb = mx.nd.array(X).astype("bfloat16")
    exact = mx.nd.gelu(xb, approximate=False).asnumpy().astype(np.float32)
    approx = mx.nd.gelu(xb, approximate=True).asnumpy().astype(np.float32)
    assert exact.dtype == np.float32 and xb.dtype == jnp.bfloat16.dtype
    np.testing.assert_allclose(exact, approx, rtol=1e-2, atol=1e-2)


def test_leaky_relu_gelu_attr_routes_both_forms():
    x = mx.nd.array(X)
    erf_out = mx.nd.LeakyReLU(x, act_type="gelu").asnumpy()
    tanh_out = mx.nd.LeakyReLU(x, act_type="gelu",
                               approximate=True).asnumpy()
    np.testing.assert_array_equal(
        erf_out, mx.nd.gelu(x, approximate=False).asnumpy())
    np.testing.assert_array_equal(
        tanh_out, mx.nd.gelu(x, approximate=True).asnumpy())


def test_gluon_gelu_block_approximate_arg():
    x = mx.nd.array(X)
    exact = nn.GELU()(x).asnumpy()
    approx = nn.GELU(approximate=True)(x).asnumpy()
    np.testing.assert_array_equal(
        exact, mx.nd.gelu(x, approximate=False).asnumpy())
    np.testing.assert_array_equal(
        approx, mx.nd.gelu(x, approximate=True).asnumpy())
    assert "approximate=True" in repr(nn.GELU(approximate=True))


def test_gelu_tanh_knob_flips_defaults(monkeypatch):
    """MXNET_GELU_TANH=1 makes the DEFAULT (no explicit attr) pick the
    tanh form in ops and new GELU blocks; an explicit approximate= always
    wins over the knob.  Fresh shapes force fresh traces so the knob is
    read under the patched environment."""
    monkeypatch.setenv("MXNET_GELU_TANH", "1")
    xk = np.linspace(-3.0, 3.0, 41, dtype=np.float32)   # unseen shape
    x = mx.nd.array(xk)
    want_tanh = _tanh_gelu(xk.astype(np.float64))
    np.testing.assert_allclose(mx.nd.gelu(x).asnumpy(), want_tanh,
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(
        mx.nd.LeakyReLU(x, act_type="gelu").asnumpy(), want_tanh,
        rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(nn.GELU()(x).asnumpy(), want_tanh,
                               rtol=1e-5, atol=1e-6)
    # explicit attr beats the knob
    out = mx.nd.gelu(x, approximate=False).asnumpy()
    assert np.max(np.abs(out - want_tanh)) > 1e-6
    out = nn.GELU(approximate=False)(x).asnumpy()
    assert np.max(np.abs(out - want_tanh)) > 1e-6
