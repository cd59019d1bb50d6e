"""Elementwise unary + broadcast binary + scalar operators.

Rebuild of the reference op families in
src/operator/tensor/elemwise_unary_op_basic.cc,
elemwise_binary_broadcast_op_{basic,extended,logic}.cc and the *_scalar ops
(src/operator/tensor/elemwise_binary_scalar_op_*.cc).  Names follow the
reference registry (``broadcast_add``, ``_plus_scalar``, ``relu`` …) so the
generated ``mx.nd.*`` namespace matches.  Kernels are jax.numpy — XLA fuses
chains of these into single TPU kernels, which is the rebuild's answer to the
reference's RTC pointwise fusion (N8): no hand-written fusion needed.
"""

from __future__ import annotations

import numpy as _np

from .registry import register


def _jnp():
    import jax.numpy as jnp
    return jnp


def _unary(name, f, differentiable=True, **kw):
    def impl(x):
        return f(_jnp(), x)
    impl.__name__ = name
    register(name, differentiable=differentiable, **kw)(impl)


# -- unary math (reference elemwise_unary_op_basic / _trig / _pow) ----------
_unary("abs", lambda jnp, x: jnp.abs(x))
_unary("sign", lambda jnp, x: jnp.sign(x))
_unary("negative", lambda jnp, x: -x)
_unary("reciprocal", lambda jnp, x: 1.0 / x)
_unary("square", lambda jnp, x: jnp.square(x))
_unary("sqrt", lambda jnp, x: jnp.sqrt(x))
_unary("rsqrt", lambda jnp, x: 1.0 / jnp.sqrt(x))
_unary("cbrt", lambda jnp, x: jnp.cbrt(x))
_unary("rcbrt", lambda jnp, x: 1.0 / jnp.cbrt(x))
_unary("exp", lambda jnp, x: jnp.exp(x))
_unary("expm1", lambda jnp, x: jnp.expm1(x))
_unary("log", lambda jnp, x: jnp.log(x))
_unary("log2", lambda jnp, x: jnp.log2(x))
_unary("log10", lambda jnp, x: jnp.log10(x))
_unary("log1p", lambda jnp, x: jnp.log1p(x))
_unary("sin", lambda jnp, x: jnp.sin(x))
_unary("cos", lambda jnp, x: jnp.cos(x))
_unary("tan", lambda jnp, x: jnp.tan(x))
_unary("arcsin", lambda jnp, x: jnp.arcsin(x))
_unary("arccos", lambda jnp, x: jnp.arccos(x))
_unary("arctan", lambda jnp, x: jnp.arctan(x))
_unary("sinh", lambda jnp, x: jnp.sinh(x))
_unary("cosh", lambda jnp, x: jnp.cosh(x))
_unary("tanh", lambda jnp, x: jnp.tanh(x))
_unary("arcsinh", lambda jnp, x: jnp.arcsinh(x))
_unary("arccosh", lambda jnp, x: jnp.arccosh(x))
_unary("arctanh", lambda jnp, x: jnp.arctanh(x))
_unary("degrees", lambda jnp, x: jnp.degrees(x))
_unary("radians", lambda jnp, x: jnp.radians(x))
_unary("floor", lambda jnp, x: jnp.floor(x), differentiable=False)
_unary("ceil", lambda jnp, x: jnp.ceil(x), differentiable=False)
_unary("round", lambda jnp, x: jnp.round(x), differentiable=False)
_unary("rint", lambda jnp, x: jnp.rint(x), differentiable=False)
_unary("trunc", lambda jnp, x: jnp.trunc(x), differentiable=False)
_unary("fix", lambda jnp, x: jnp.trunc(x), differentiable=False)
_unary("gamma", lambda jnp, x: _gamma_impl(jnp, x))
_unary("gammaln", lambda jnp, x: _gammaln_impl(jnp, x))
_unary("erf", lambda jnp, x: _erf_impl(jnp, x))
_unary("erfinv", lambda jnp, x: _erfinv_impl(jnp, x))
_unary("relu", lambda jnp, x: jnp.maximum(x, 0))
_unary("sigmoid", lambda jnp, x: _sigmoid_impl(jnp, x))
_unary("softsign", lambda jnp, x: x / (1.0 + jnp.abs(x)))
_unary("logical_not", lambda jnp, x: (x == 0).astype(x.dtype),
       differentiable=False)
_unary("zeros_like", lambda jnp, x: jnp.zeros_like(x), differentiable=False)
_unary("ones_like", lambda jnp, x: jnp.ones_like(x), differentiable=False)
_unary("identity", lambda jnp, x: x)
_unary("stop_gradient", lambda jnp, x: _stop_grad(x))
_unary("make_loss", lambda jnp, x: x)
_unary("isnan", lambda jnp, x: jnp.isnan(x), differentiable=False)
_unary("isinf", lambda jnp, x: jnp.isinf(x), differentiable=False)
_unary("isfinite", lambda jnp, x: jnp.isfinite(x), differentiable=False)


def _stop_grad(x):
    import jax
    return jax.lax.stop_gradient(x)


def _sigmoid_impl(jnp, x):
    import jax
    return jax.nn.sigmoid(x)


def _erf_impl(jnp, x):
    import jax
    return jax.scipy.special.erf(x)


def _erfinv_impl(jnp, x):
    import jax
    return jax.scipy.special.erfinv(x)


def _gammaln_impl(jnp, x):
    import jax
    return jax.scipy.special.gammaln(x)


def _gamma_impl(jnp, x):
    import jax
    return jnp.exp(jax.scipy.special.gammaln(x)) * jnp.sign(
        jnp.where(x > 0, 1.0, jnp.cos(jnp.pi * x)))


@register("cast")
def _cast(x, dtype=None):
    return x.astype(dtype)


@register("amp_cast")
def _amp_cast(x, dtype=None):
    return x.astype(dtype)


@register("hard_sigmoid")
def _hard_sigmoid(x, alpha=0.2, beta=0.5):
    return _jnp().clip(alpha * x + beta, 0.0, 1.0)


@register("softrelu")
def _softrelu(x):
    return _jnp().logaddexp(x, 0.0)


def _gelu_tanh_default():
    """Knob-resolved default for gelu's ``approximate`` attr (ISSUE 7
    satellite: the tanh form is an untried MFU lever, ROADMAP A3).
    Resolved when an executable is first built for the attr set (a knob
    read at trace time: a later change of the environment does not reach a
    program already built); pass an explicit ``approximate=`` (it is part
    of the jit cache key) to flip per call."""
    from .. import config
    return bool(config.get_int("MXNET_GELU_TANH", 0))


@register("gelu")
def _gelu(x, approximate=None):
    # exact erf form by default: the reference's gelu (leaky_relu.cc
    # act_type='gelu') is 0.5x(1+erf(x/√2)); approximate=True (or
    # MXNET_GELU_TANH=1) selects 0.5x(1+tanh(√(2/π)(x+0.044715x³)))
    import jax
    if approximate is None:
        approximate = _gelu_tanh_default()
    return jax.nn.gelu(x, approximate=approximate)


@register("silu")
def _silu(x):
    import jax
    return jax.nn.silu(x)


@register("shape_array", differentiable=False)
def _shape_array(x):
    return _jnp().asarray(_np.asarray(x.shape, dtype=_np.int64))


@register("size_array", differentiable=False)
def _size_array(x):
    return _jnp().asarray(_np.asarray([x.size], dtype=_np.int64))


# -- broadcast binary (reference elemwise_binary_broadcast_op_*) ------------

def _binary(name, f, differentiable=True):
    def impl(lhs, rhs):
        return f(_jnp(), lhs, rhs)
    impl.__name__ = name
    register(name, differentiable=differentiable)(impl)


_binary("broadcast_add", lambda jnp, a, b: a + b)
_binary("broadcast_sub", lambda jnp, a, b: a - b)
_binary("broadcast_mul", lambda jnp, a, b: a * b)
_binary("broadcast_div", lambda jnp, a, b: a / b)
_binary("broadcast_floor_div", lambda jnp, a, b: jnp.floor_divide(a, b),
        differentiable=False)
_binary("broadcast_mod", lambda jnp, a, b: jnp.mod(a, b))
_binary("broadcast_power", lambda jnp, a, b: jnp.power(a, b))
_binary("broadcast_maximum", lambda jnp, a, b: jnp.maximum(a, b))
_binary("broadcast_minimum", lambda jnp, a, b: jnp.minimum(a, b))
_binary("broadcast_hypot", lambda jnp, a, b: jnp.hypot(a, b))
_binary("broadcast_equal", lambda jnp, a, b: (a == b).astype(a.dtype),
        differentiable=False)
_binary("broadcast_not_equal", lambda jnp, a, b: (a != b).astype(a.dtype),
        differentiable=False)
_binary("broadcast_greater", lambda jnp, a, b: (a > b).astype(a.dtype),
        differentiable=False)
_binary("broadcast_greater_equal", lambda jnp, a, b: (a >= b).astype(a.dtype),
        differentiable=False)
_binary("broadcast_lesser", lambda jnp, a, b: (a < b).astype(a.dtype),
        differentiable=False)
_binary("broadcast_lesser_equal", lambda jnp, a, b: (a <= b).astype(a.dtype),
        differentiable=False)
_binary("broadcast_logical_and", lambda jnp, a, b:
        jnp.logical_and(a != 0, b != 0).astype(a.dtype), differentiable=False)
_binary("broadcast_logical_or", lambda jnp, a, b:
        jnp.logical_or(a != 0, b != 0).astype(a.dtype), differentiable=False)
_binary("broadcast_logical_xor", lambda jnp, a, b:
        jnp.logical_xor(a != 0, b != 0).astype(a.dtype), differentiable=False)

# narrow (non-broadcast) aliases the reference also registers
for _alias, _target in [("elemwise_add", "broadcast_add"),
                        ("elemwise_sub", "broadcast_sub"),
                        ("elemwise_mul", "broadcast_mul"),
                        ("elemwise_div", "broadcast_div")]:
    from .registry import get as _get

    def _mk(tname):
        def impl(lhs, rhs):
            return _get(tname).fn(lhs, rhs)
        return impl
    register(_alias)(_mk(_target))


@register("add_n")
def _add_n(*args):
    out = args[0]
    for a in args[1:]:
        out = out + a
    return out


@register("maximum")
def _maximum(lhs, rhs):
    return _jnp().maximum(lhs, rhs)


@register("minimum")
def _minimum(lhs, rhs):
    return _jnp().minimum(lhs, rhs)


@register("smooth_l1")
def _smooth_l1(x, scalar=1.0):
    jnp = _jnp()
    s2 = scalar * scalar
    return _jnp().where(_jnp().abs(x) < 1.0 / s2,
                        0.5 * s2 * x * x,
                        jnp.abs(x) - 0.5 / s2)


# -- scalar ops (reference *_scalar family; `reverse` handles rsub/rdiv) ----

def _scalar(name, f, differentiable=True):
    def impl(x, scalar=0.0, reverse=False):
        jnp = _jnp()
        s = jnp.asarray(scalar, dtype=x.dtype)
        return f(jnp, s, x) if reverse else f(jnp, x, s)
    impl.__name__ = name
    register(name, differentiable=differentiable)(impl)


_scalar("_plus_scalar", lambda jnp, a, b: a + b)
_scalar("_minus_scalar", lambda jnp, a, b: a - b)
_scalar("_mul_scalar", lambda jnp, a, b: a * b)
_scalar("_div_scalar", lambda jnp, a, b: a / b)
_scalar("_floor_div_scalar", lambda jnp, a, b: jnp.floor_divide(a, b),
        differentiable=False)
_scalar("_mod_scalar", lambda jnp, a, b: jnp.mod(a, b))
_scalar("_power_scalar", lambda jnp, a, b: jnp.power(a, b))
_scalar("_maximum_scalar", lambda jnp, a, b: jnp.maximum(a, b))
_scalar("_minimum_scalar", lambda jnp, a, b: jnp.minimum(a, b))
_scalar("_hypot_scalar", lambda jnp, a, b: jnp.hypot(a, b))
_scalar("_equal_scalar", lambda jnp, a, b: (a == b).astype(a.dtype),
        differentiable=False)
_scalar("_not_equal_scalar", lambda jnp, a, b: (a != b).astype(a.dtype),
        differentiable=False)
_scalar("_greater_scalar", lambda jnp, a, b: (a > b).astype(a.dtype),
        differentiable=False)
_scalar("_greater_equal_scalar", lambda jnp, a, b: (a >= b).astype(a.dtype),
        differentiable=False)
_scalar("_lesser_scalar", lambda jnp, a, b: (a < b).astype(a.dtype),
        differentiable=False)
_scalar("_lesser_equal_scalar", lambda jnp, a, b: (a <= b).astype(a.dtype),
        differentiable=False)


@register("clip")
def _clip(x, a_min=None, a_max=None):
    return _jnp().clip(x, a_min, a_max)


@register("digamma")
def _digamma(x):
    import jax
    return jax.scipy.special.digamma(x)


@register("log_sigmoid")
def _log_sigmoid(x):
    """reference 1.8 log_sigmoid: log(1/(1+exp(-x))) = -softplus(-x)."""
    import jax
    return -jax.nn.softplus(-x)


@register("mish")
def _mish(x):
    """reference 1.8 mish: x * tanh(softplus(x))."""
    import jax
    return x * _jnp().tanh(jax.nn.softplus(x))


@register("amp_multicast", num_outputs=-1)
def _amp_multicast(*data, num_outputs=0, cast_narrow=False):  # noqa: ARG001
    """reference amp_multicast: cast every input to a COMMON dtype — the
    widest float present (or the narrowest with cast_narrow), the AMP
    pass's multi-input harmonizer."""
    jnp = _jnp()
    floats = [d.dtype for d in data
              if jnp.issubdtype(d.dtype, jnp.floating)]
    if not floats:
        return list(data)
    order = sorted(floats, key=lambda t: jnp.finfo(t).bits)
    common = order[0] if cast_narrow else order[-1]
    return [d.astype(common) for d in data]
