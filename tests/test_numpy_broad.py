"""Broad table-driven mx.np ↔ numpy parity sweep (reference
test_numpy_op.py's per-op coverage style, P3/N7 numpy families).

Each case runs the mx.np function and the same-named numpy function on
identical inputs and asserts elementwise agreement — ~90 functions across
unary/binary/reduction/shape/linalg families, plus np.random statistical
checks and npx.set_np semantics."""

import numpy as onp
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd

np = mx.np


def _r(shape, seed=0, positive=False, small=False):
    r = onp.random.RandomState(seed)
    x = r.randn(*shape).astype(onp.float32)
    if positive:
        x = onp.abs(x) + 0.1
    if small:
        x = x * 0.4
    return x


UNARY = [
    ("exp", {}), ("expm1", {}), ("log", {"positive": True}),
    ("log2", {"positive": True}), ("log10", {"positive": True}),
    ("log1p", {"positive": True}), ("sqrt", {"positive": True}),
    ("cbrt", {}), ("square", {}), ("abs", {}), ("sign", {}),
    ("floor", {}), ("ceil", {}), ("trunc", {}), ("rint", {}),
    ("sin", {}), ("cos", {}), ("tan", {"small": True}),
    ("arcsin", {"small": True}), ("arccos", {"small": True}),
    ("arctan", {}), ("sinh", {}), ("cosh", {}), ("tanh", {}),
    ("arcsinh", {}), ("arctanh", {"small": True}),
    ("degrees", {}), ("radians", {}), ("reciprocal", {"positive": True}),
    ("negative", {}), ("exp2", {"small": True}),
]


@pytest.mark.parametrize("name,opts", UNARY, ids=[u[0] for u in UNARY])
def test_np_unary(name, opts):
    if not hasattr(np, name) or not hasattr(onp, name):
        pytest.skip(f"{name} not on both surfaces")
    x = _r((3, 5), positive=opts.get("positive", False),
           small=opts.get("small", False))
    got = getattr(np, name)(np.array(x)).asnumpy()
    want = getattr(onp, name)(x)
    onp.testing.assert_allclose(got, want, rtol=2e-5, atol=2e-6)


BINARY = ["add", "subtract", "multiply", "divide", "power", "maximum",
          "minimum", "hypot", "arctan2", "fmod", "copysign",
          "greater", "greater_equal", "less", "less_equal", "equal",
          "not_equal", "logaddexp"]


@pytest.mark.parametrize("name", BINARY)
def test_np_binary(name):
    if not hasattr(np, name) or not hasattr(onp, name):
        pytest.skip(f"{name} not on both surfaces")
    a = onp.abs(_r((4, 3), 1)) + 0.5
    b = onp.abs(_r((4, 3), 2)) + 0.5
    got = getattr(np, name)(np.array(a), np.array(b)).asnumpy()
    want = getattr(onp, name)(a, b)
    onp.testing.assert_allclose(onp.asarray(got, want.dtype), want,
                                rtol=2e-5, atol=2e-6)


REDUCTIONS = ["sum", "prod", "mean", "std", "var", "max", "min",
              "argmax", "argmin", "cumsum", "cumprod"]


@pytest.mark.parametrize("name", REDUCTIONS)
@pytest.mark.parametrize("axis", [None, 0, 1])
def test_np_reductions(name, axis):
    x = onp.abs(_r((3, 4), 3)) * 0.5 + 0.5
    got = getattr(np, name)(np.array(x), axis=axis).asnumpy()
    want = getattr(onp, name)(x, axis=axis)
    onp.testing.assert_allclose(onp.asarray(got, dtype=want.dtype), want,
                                rtol=2e-5, atol=1e-5)


SHAPE_FNS = [
    ("reshape", lambda m, x: m.reshape(m.array(x), (6, 2)),
     lambda x: onp.reshape(x, (6, 2))),
    ("transpose", lambda m, x: m.transpose(m.array(x)),
     lambda x: onp.transpose(x)),
    ("concatenate", lambda m, x: m.concatenate([m.array(x), m.array(x)],
                                               axis=0),
     lambda x: onp.concatenate([x, x], axis=0)),
    ("stack", lambda m, x: m.stack([m.array(x), m.array(x)], axis=1),
     lambda x: onp.stack([x, x], axis=1)),
    ("split", lambda m, x: m.split(m.array(x), 2, axis=0)[1],
     lambda x: onp.split(x, 2, axis=0)[1]),
    ("flip", lambda m, x: m.flip(m.array(x), axis=1),
     lambda x: onp.flip(x, axis=1)),
    ("roll", lambda m, x: m.roll(m.array(x), 2, axis=0),
     lambda x: onp.roll(x, 2, axis=0)),
    ("tile", lambda m, x: m.tile(m.array(x), (2, 1)),
     lambda x: onp.tile(x, (2, 1))),
    ("repeat", lambda m, x: m.repeat(m.array(x), 2, axis=1),
     lambda x: onp.repeat(x, 2, axis=1)),
    ("expand_dims", lambda m, x: m.expand_dims(m.array(x), 0),
     lambda x: onp.expand_dims(x, 0)),
    ("squeeze", lambda m, x: m.squeeze(m.expand_dims(m.array(x), 0)),
     lambda x: x),
    ("where", lambda m, x: m.where(m.array(x) > 0, m.array(x),
                                   m.zeros_like(m.array(x))),
     lambda x: onp.where(x > 0, x, onp.zeros_like(x))),
    ("clip", lambda m, x: m.clip(m.array(x), -0.5, 0.5),
     lambda x: onp.clip(x, -0.5, 0.5)),
    ("sort", lambda m, x: m.sort(m.array(x), axis=1),
     lambda x: onp.sort(x, axis=1)),
    ("argsort", lambda m, x: m.argsort(m.array(x), axis=1),
     lambda x: onp.argsort(x, axis=1)),
    ("unique", lambda m, x: m.unique(m.array(onp.round(x))),
     lambda x: onp.unique(onp.round(x))),
    ("diff", lambda m, x: m.diff(m.array(x), axis=1),
     lambda x: onp.diff(x, axis=1)),
    ("pad", lambda m, x: m.pad(m.array(x), ((1, 1), (0, 0))),
     lambda x: onp.pad(x, ((1, 1), (0, 0)))),
    ("trace", lambda m, x: m.trace(m.array(x)),
     lambda x: onp.trace(x)),
    ("outer", lambda m, x: m.outer(m.array(x[0]), m.array(x[1])),
     lambda x: onp.outer(x[0], x[1])),
    ("einsum", lambda m, x: m.einsum("ij,kj->ik", m.array(x), m.array(x)),
     lambda x: onp.einsum("ij,kj->ik", x, x)),
    ("dot", lambda m, x: m.dot(m.array(x), m.array(x.T)),
     lambda x: onp.dot(x, x.T)),
    ("matmul", lambda m, x: m.matmul(m.array(x), m.array(x.T)),
     lambda x: onp.matmul(x, x.T)),
    ("tensordot", lambda m, x: m.tensordot(m.array(x), m.array(x),
                                           axes=([1], [1])),
     lambda x: onp.tensordot(x, x, axes=([1], [1]))),
    ("kron", lambda m, x: m.kron(m.array(x[:2, :2]), m.array(x[:2, :2])),
     lambda x: onp.kron(x[:2, :2], x[:2, :2])),
    ("meshgrid", lambda m, x: m.meshgrid(m.array(x[0]), m.array(x[1]))[0],
     lambda x: onp.meshgrid(x[0], x[1])[0]),
    ("atleast_2d", lambda m, x: m.atleast_2d(m.array(x[0])),
     lambda x: onp.atleast_2d(x[0])),
    ("ravel", lambda m, x: m.ravel(m.array(x)),
     lambda x: onp.ravel(x)),
    ("triu", lambda m, x: m.triu(m.array(x)), lambda x: onp.triu(x)),
    ("tril", lambda m, x: m.tril(m.array(x)), lambda x: onp.tril(x)),
]


@pytest.mark.parametrize("case", SHAPE_FNS, ids=[c[0] for c in SHAPE_FNS])
def test_np_shape_and_linalgish(case):
    name, mx_fn, onp_fn = case
    if not hasattr(np, name):
        pytest.skip(f"mx.np.{name} absent")
    x = _r((4, 3), 7)
    got = mx_fn(np, x)
    got = got.asnumpy() if hasattr(got, "asnumpy") else onp.asarray(got)
    want = onp_fn(x)
    onp.testing.assert_allclose(onp.asarray(got, dtype=want.dtype), want,
                                rtol=2e-5, atol=2e-6)


LINALG = [
    ("norm", lambda m, a: m.linalg.norm(a), lambda a: onp.linalg.norm(a)),
    ("det", lambda m, a: m.linalg.det(a), lambda a: onp.linalg.det(a)),
    ("inv", lambda m, a: m.linalg.inv(a), lambda a: onp.linalg.inv(a)),
    ("slogdet", lambda m, a: m.linalg.slogdet(a)[1],
     lambda a: onp.linalg.slogdet(a)[1]),
    ("solve", lambda m, a: m.linalg.solve(a, m.ones((3, 1))
                                          if hasattr(m, 'ones') else None),
     lambda a: onp.linalg.solve(a, onp.ones((3, 1), onp.float32))),
    ("cholesky", lambda m, a: m.linalg.cholesky(a),
     lambda a: onp.linalg.cholesky(a)),
    ("eigvalsh", lambda m, a: m.linalg.eigvalsh(a),
     lambda a: onp.linalg.eigvalsh(a)),
    ("matrix_rank", lambda m, a: m.linalg.matrix_rank(a),
     lambda a: onp.linalg.matrix_rank(a)),
    ("pinv", lambda m, a: m.linalg.pinv(a), lambda a: onp.linalg.pinv(a)),
]


@pytest.mark.parametrize("case", LINALG, ids=[c[0] for c in LINALG])
def test_np_linalg(case):
    name, mx_fn, onp_fn = case
    if not hasattr(np.linalg, name):
        pytest.skip(f"mx.np.linalg.{name} absent")
    r = onp.random.RandomState(11)
    a = r.randn(3, 3).astype(onp.float32)
    spd = (a @ a.T + 3 * onp.eye(3)).astype(onp.float32)  # SPD for chol etc.
    got = mx_fn(np, np.array(spd))
    got = got.asnumpy() if hasattr(got, "asnumpy") else onp.asarray(got)
    want = onp_fn(spd)
    onp.testing.assert_allclose(got, onp.asarray(want), rtol=5e-4,
                                atol=5e-5)


def test_np_random_statistics():
    mx.random.seed(7)
    u = np.random.uniform(0, 1, size=(20000,)).asnumpy()
    assert 0.48 < u.mean() < 0.52
    assert u.min() >= 0 and u.max() <= 1
    g = np.random.normal(2.0, 3.0, size=(20000,)).asnumpy()
    assert abs(g.mean() - 2.0) < 0.1
    assert abs(g.std() - 3.0) < 0.1
    ri = np.random.randint(0, 10, size=(5000,)).asnumpy()
    assert set(onp.unique(ri)) <= set(range(10))


def test_np_autograd_through_np_functions():
    """mx.np functions record on the imperative tape like nd ops."""
    x = np.array(_r((3, 3), 13))
    x.attach_grad()
    with autograd.record():
        y = np.sum(np.tanh(x) * np.exp(x * 0.1))
    y.backward()
    g = x.grad.asnumpy()
    xv = x.asnumpy()
    want = (1 - onp.tanh(xv) ** 2) * onp.exp(xv * 0.1) \
        + onp.tanh(xv) * 0.1 * onp.exp(xv * 0.1)
    onp.testing.assert_allclose(g, want, rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# delegated-surface parity extension (ISSUE 8 satellite):
# a representative ~30-function slice across the three behavioral axes the
# thin delegation could silently get wrong — dtype promotion, axis kwargs
# (tuple / negative / keepdims), and python-scalar / 0-d operands.
# ---------------------------------------------------------------------------

# dtype pairs where numpy and the XLA lattice agree (int32+float32 is
# deliberately absent: numpy value-promotes it to float64, which the
# x64-disabled backend cannot represent — a documented divergence)
_PROMO_PAIRS = [("int16", "float32"), ("int8", "float32"),
                ("int8", "int32"), ("uint8", "int32"),
                ("bool", "int32"), ("int32", "int32"),
                ("float32", "float32")]
_PROMO_FNS = ["add", "subtract", "multiply", "maximum", "minimum"]


@pytest.mark.parametrize("da,db", _PROMO_PAIRS,
                         ids=[f"{a}+{b}" for a, b in _PROMO_PAIRS])
@pytest.mark.parametrize("name", _PROMO_FNS)
def test_np_dtype_promotion(name, da, db):
    av = onp.array([1, 0, 3]).astype(da)
    bv = onp.array([2, 5, 1]).astype(db)
    got = getattr(np, name)(np.array(av), np.array(bv)).asnumpy()
    want = getattr(onp, name)(av, bv)
    assert onp.dtype(got.dtype) == want.dtype, \
        f"{name}({da},{db}): promoted to {got.dtype}, numpy {want.dtype}"
    onp.testing.assert_array_equal(got, want)


def test_np_division_promotes_ints_to_float():
    """true_divide of ints must yield a float (numpy: float64; here the
    x64-disabled analog float32) with numpy's values."""
    a = np.array(onp.array([7, 8, 9], onp.int32))
    b = np.array(onp.array([2, 4, 3], onp.int32))
    got = np.divide(a, b).asnumpy()
    assert onp.dtype(got.dtype).kind == "f"
    onp.testing.assert_allclose(
        got, onp.divide(onp.array([7, 8, 9]), onp.array([2, 4, 3])),
        rtol=1e-6)


_AXIS_FNS = ["sum", "mean", "prod", "std", "var", "max", "min"]


@pytest.mark.parametrize("axis", [(0, 2), (1,), -1, -2, None],
                         ids=["tuple02", "tuple1", "neg1", "neg2", "none"])
@pytest.mark.parametrize("keepdims", [False, True])
@pytest.mark.parametrize("name", _AXIS_FNS)
def test_np_reduction_axis_kwargs(name, axis, keepdims):
    x = onp.abs(_r((2, 3, 4), 21)) + 0.5
    got = getattr(np, name)(np.array(x), axis=axis,
                            keepdims=keepdims).asnumpy()
    want = getattr(onp, name)(x, axis=axis, keepdims=keepdims)
    assert got.shape == want.shape, \
        f"{name} axis={axis} keepdims={keepdims}: {got.shape} vs {want.shape}"
    onp.testing.assert_allclose(onp.asarray(got, want.dtype), want,
                                rtol=2e-5, atol=1e-5)


@pytest.mark.parametrize("name", ["argmax", "argmin", "cumsum"])
@pytest.mark.parametrize("axis", [-1, 0])
def test_np_index_and_scan_negative_axis(name, axis):
    x = _r((3, 4), 22)
    got = getattr(np, name)(np.array(x), axis=axis).asnumpy()
    want = getattr(onp, name)(x, axis=axis)
    if name == "cumsum":  # XLA's log-depth scan reassociates the sum
        onp.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
    else:
        onp.testing.assert_array_equal(got, want)


_SCALAR_CASES = [
    ("add", lambda m, a: m.add(a, 2)),
    ("subtract", lambda m, a: m.subtract(a, 1.5)),
    ("multiply", lambda m, a: m.multiply(a, 3)),
    ("divide", lambda m, a: m.divide(a, 2.0)),
    ("power", lambda m, a: m.power(a, 2)),
    ("maximum", lambda m, a: m.maximum(a, 1.5)),
    ("minimum", lambda m, a: m.minimum(a, 1.5)),
    ("mod", lambda m, a: m.mod(a, 3)),
    ("floor_divide", lambda m, a: m.floor_divide(a, 3)),
    ("arctan2", lambda m, a: m.arctan2(a, 2.0)),
]


@pytest.mark.parametrize("case", _SCALAR_CASES,
                         ids=[c[0] for c in _SCALAR_CASES])
def test_np_python_scalar_operand(case):
    name, fn = case
    x = onp.abs(_r((3, 4), 23)) + 1.0
    got = fn(np, np.array(x)).asnumpy()
    want = fn(onp, x)
    onp.testing.assert_allclose(onp.asarray(got, want.dtype), want,
                                rtol=2e-5, atol=2e-6)


def test_np_zero_d_arrays():
    """0-d arrays flow through unary/binary/reduction like numpy's."""
    z = np.array(3.5)
    assert z.shape == ()
    assert float(np.add(z, 1.5).asnumpy()) == 5.0
    assert float(np.exp(np.array(0.0)).asnumpy()) == 1.0
    # reducing a 0-d array is the identity, as in numpy
    assert float(np.sum(z).asnumpy()) == 3.5
    assert np.sum(z).shape == ()
    # reducing a 1-d array to 0-d round-trips through python float
    s = np.sum(np.array(onp.ones(4, onp.float32)))
    assert s.shape == () and float(s.asnumpy()) == 4.0
    # 0-d broadcasts against arrays like a scalar
    got = np.multiply(np.array(onp.array([1.0, 2.0], onp.float32)), z)
    onp.testing.assert_allclose(got.asnumpy(), [3.5, 7.0])


# ---------------------------------------------------------------------------
# delegated-surface parity extension round 2 (ISSUE 11 satellite): another
# ~34-function slice — searching/counting, nan-aware statistics, logic
# predicates, integer/bit math, construction, and axis manipulation —
# the families where thin jnp delegation could silently diverge from
# numpy (bool/int result dtypes, nan propagation, negative-axis moves).
# ---------------------------------------------------------------------------

def _xnan():
    x = _r((3, 4), 31)
    x[0, 1] = onp.nan
    x[2, 2] = onp.inf
    return x


EXT_FNS = [
    ("searchsorted",
     lambda m, x: m.searchsorted(m.sort(m.array(x.ravel())),
                                 m.array(x[0])),
     lambda x: onp.searchsorted(onp.sort(x.ravel()), x[0])),
    ("count_nonzero",
     lambda m, x: m.count_nonzero(m.array(x) > 0, axis=1),
     lambda x: onp.count_nonzero(x > 0, axis=1)),
    ("nonzero",
     lambda m, x: m.nonzero(m.array(x) > 0)[0],
     lambda x: onp.nonzero(x > 0)[0]),
    ("flatnonzero",
     lambda m, x: m.flatnonzero(m.array(x) > 0),
     lambda x: onp.flatnonzero(x > 0)),
    ("argwhere",
     lambda m, x: m.argwhere(m.array(x) > 0),
     lambda x: onp.argwhere(x > 0)),
    ("median", lambda m, x: m.median(m.array(x), axis=1),
     lambda x: onp.median(x, axis=1)),
    ("percentile", lambda m, x: m.percentile(m.array(x), 30, axis=0),
     lambda x: onp.percentile(x, 30, axis=0)),
    ("quantile", lambda m, x: m.quantile(m.array(x), 0.7),
     lambda x: onp.quantile(x, 0.7)),
    ("average",
     lambda m, x: m.average(m.array(x), axis=1),
     lambda x: onp.average(x, axis=1)),
    ("ptp", lambda m, x: m.ptp(m.array(x), axis=0),
     lambda x: onp.ptp(x, axis=0)),
    ("nanmean", lambda m, x: m.nanmean(m.array(_xnan()), axis=0),
     lambda x: onp.nanmean(_xnan(), axis=0)),
    ("nansum", lambda m, x: m.nansum(m.array(_xnan()), axis=1),
     lambda x: onp.nansum(_xnan(), axis=1)),
    ("nanmax", lambda m, x: m.nanmax(m.array(_xnan()[:2]), axis=1),
     lambda x: onp.nanmax(_xnan()[:2], axis=1)),
    ("nanstd", lambda m, x: m.nanstd(m.array(_xnan()[:2]), axis=1),
     lambda x: onp.nanstd(_xnan()[:2], axis=1)),
    ("isnan", lambda m, x: m.isnan(m.array(_xnan())),
     lambda x: onp.isnan(_xnan())),
    ("isinf", lambda m, x: m.isinf(m.array(_xnan())),
     lambda x: onp.isinf(_xnan())),
    ("isfinite", lambda m, x: m.isfinite(m.array(_xnan())),
     lambda x: onp.isfinite(_xnan())),
    ("signbit", lambda m, x: m.signbit(m.array(x)),
     lambda x: onp.signbit(x)),
    ("logical_and",
     lambda m, x: m.logical_and(m.array(x) > 0, m.array(x) < 1),
     lambda x: onp.logical_and(x > 0, x < 1)),
    ("logical_or",
     lambda m, x: m.logical_or(m.array(x) > 1, m.array(x) < -1),
     lambda x: onp.logical_or(x > 1, x < -1)),
    ("logical_xor",
     lambda m, x: m.logical_xor(m.array(x) > 0, m.array(x) > 1),
     lambda x: onp.logical_xor(x > 0, x > 1)),
    ("logical_not", lambda m, x: m.logical_not(m.array(x) > 0),
     lambda x: onp.logical_not(x > 0)),
    ("isclose",
     lambda m, x: m.isclose(m.array(x), m.array(x + 1e-7)),
     lambda x: onp.isclose(x, x + 1e-7)),
    ("fmax", lambda m, x: m.fmax(m.array(x), m.array(-x)),
     lambda x: onp.fmax(x, -x)),
    ("fmin", lambda m, x: m.fmin(m.array(x), m.array(-x)),
     lambda x: onp.fmin(x, -x)),
    ("fabs", lambda m, x: m.fabs(m.array(x)), lambda x: onp.fabs(x)),
    ("heaviside", lambda m, x: m.heaviside(m.array(x), 0.5),
     lambda x: onp.heaviside(x, onp.float32(0.5))),
    ("nan_to_num", lambda m, x: m.nan_to_num(m.array(_xnan())),
     lambda x: onp.nan_to_num(_xnan())),
    ("ldexp",
     lambda m, x: m.ldexp(m.array(x),
                          m.array(onp.arange(5, dtype=onp.int32))),
     lambda x: onp.ldexp(x, onp.arange(5, dtype=onp.int32))),
    ("gcd",
     lambda m, x: m.gcd(m.array(onp.array([12, 18, 7], onp.int32)),
                        m.array(onp.array([8, 27, 21], onp.int32))),
     lambda x: onp.gcd(onp.array([12, 18, 7], onp.int32),
                       onp.array([8, 27, 21], onp.int32))),
    ("lcm",
     lambda m, x: m.lcm(m.array(onp.array([4, 6, 5], onp.int32)),
                        m.array(onp.array([6, 8, 7], onp.int32))),
     lambda x: onp.lcm(onp.array([4, 6, 5], onp.int32),
                       onp.array([6, 8, 7], onp.int32))),
    ("linspace", lambda m, x: m.linspace(-2.0, 2.0, 9),
     lambda x: onp.linspace(-2.0, 2.0, 9).astype(onp.float32)),
    ("logspace", lambda m, x: m.logspace(0.0, 2.0, 5),
     lambda x: onp.logspace(0.0, 2.0, 5).astype(onp.float32)),
    ("eye", lambda m, x: m.eye(4, 5, 1), lambda x: onp.eye(4, 5, 1)),
    ("tri", lambda m, x: m.tri(4, 4, -1), lambda x: onp.tri(4, 4, -1)),
    ("diag", lambda m, x: m.diag(m.diag(m.array(x[:3, :3]))),
     lambda x: onp.diag(onp.diag(x[:3, :3]))),
    ("rot90", lambda m, x: m.rot90(m.array(x)),
     lambda x: onp.rot90(x)),
    ("fliplr", lambda m, x: m.fliplr(m.array(x)),
     lambda x: onp.fliplr(x)),
    ("flipud", lambda m, x: m.flipud(m.array(x)),
     lambda x: onp.flipud(x)),
    ("moveaxis",
     lambda m, x: m.moveaxis(m.array(x[:, :3].reshape(2, 2, 3)), 0, -1),
     lambda x: onp.moveaxis(x[:, :3].reshape(2, 2, 3), 0, -1)),
    ("swapaxes", lambda m, x: m.swapaxes(m.array(x), 0, 1),
     lambda x: onp.swapaxes(x, 0, 1)),
    ("broadcast_to",
     lambda m, x: m.broadcast_to(m.array(x[0]), (3, 5)),
     lambda x: onp.broadcast_to(x[0], (3, 5))),
    ("bincount",
     lambda m, x: m.bincount(m.array(onp.array([0, 1, 1, 3, 2, 1],
                                               onp.int32))),
     lambda x: onp.bincount(onp.array([0, 1, 1, 3, 2, 1], onp.int32))),
    ("digitize",
     lambda m, x: m.digitize(m.array(x),
                             m.array(onp.array([-1.0, 0.0, 1.0],
                                               onp.float32))),
     lambda x: onp.digitize(x, onp.array([-1.0, 0.0, 1.0], onp.float32))),
    ("interp",
     lambda m, x: m.interp(m.array(x.ravel()),
                           m.array(onp.array([-2.0, 0.0, 2.0],
                                             onp.float32)),
                           m.array(onp.array([0.0, 1.0, 4.0],
                                             onp.float32))),
     lambda x: onp.interp(x.ravel(),
                          onp.array([-2.0, 0.0, 2.0], onp.float32),
                          onp.array([0.0, 1.0, 4.0], onp.float32))),
    ("cross",
     lambda m, x: m.cross(m.array(x[:, :3]), m.array(x[:, 1:4])),
     lambda x: onp.cross(x[:, :3], x[:, 1:4])),
    ("corrcoef", lambda m, x: m.corrcoef(m.array(x)),
     lambda x: onp.corrcoef(x)),
    ("cov", lambda m, x: m.cov(m.array(x)), lambda x: onp.cov(x)),
    ("ediff1d", lambda m, x: m.ediff1d(m.array(x)),
     lambda x: onp.ediff1d(x)),
    ("array_split",
     lambda m, x: m.array_split(m.array(x), 3, axis=1)[1],
     lambda x: onp.array_split(x, 3, axis=1)[1]),
    ("column_stack",
     lambda m, x: m.column_stack([m.array(x[0]), m.array(x[1])]),
     lambda x: onp.column_stack([x[0], x[1]])),
    ("dstack", lambda m, x: m.dstack([m.array(x), m.array(x)]),
     lambda x: onp.dstack([x, x])),
    ("take_along_axis",
     lambda m, x: m.take_along_axis(m.array(x),
                                    m.argsort(m.array(x), axis=1), 1),
     lambda x: onp.take_along_axis(x, onp.argsort(x, axis=1), 1)),
    ("float_power",
     lambda m, x: m.float_power(m.array(onp.abs(x) + 0.5), 2.5),
     lambda x: onp.float_power(onp.abs(x) + 0.5, 2.5)),
    ("remainder",
     lambda m, x: m.remainder(m.array(x), 0.75),
     lambda x: onp.remainder(x, onp.float32(0.75))),
]


@pytest.mark.parametrize("case", EXT_FNS, ids=[c[0] for c in EXT_FNS])
def test_np_extended_surface(case):
    name, mx_fn, onp_fn = case
    if not hasattr(np, name):
        pytest.skip(f"mx.np.{name} absent")
    x = _r((4, 5), 29)
    got = mx_fn(np, x)
    got = got.asnumpy() if hasattr(got, "asnumpy") else onp.asarray(got)
    want = onp_fn(x)
    assert got.shape == onp.asarray(want).shape, \
        f"{name}: shape {got.shape} vs numpy {onp.asarray(want).shape}"
    if onp.asarray(want).dtype.kind == "b":
        assert onp.dtype(got.dtype).kind == "b", \
            f"{name}: bool result came back as {got.dtype}"
        onp.testing.assert_array_equal(got, want)
    elif onp.asarray(want).dtype.kind in "iu":
        assert onp.dtype(got.dtype).kind in "iu", \
            f"{name}: integer result came back as {got.dtype}"
        onp.testing.assert_array_equal(got, onp.asarray(want))
    else:
        onp.testing.assert_allclose(
            onp.asarray(got, onp.asarray(want).dtype), want,
            rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# delegated-surface parity extension round 3 (ISSUE 12 satellite): another
# ~34-function slice — array surgery (append/delete/insert/splits),
# selection (compress/extract/select/choose/piecewise), products
# (inner/vdot/convolve/correlate), index constructors, complex-view and
# sign helpers, the nan-aware argmin/argmax/cum family, and predicate
# reducers — again targeting the spots where thin jnp delegation could
# silently diverge (int/bool result dtypes, axis conventions, nan rules).
# ---------------------------------------------------------------------------

EXT_FNS3 = [
    ("append",
     lambda m, x: m.append(m.array(x), m.array(x[:1]), axis=0),
     lambda x: onp.append(x, x[:1], axis=0)),
    ("delete", lambda m, x: m.delete(m.array(x), 2, axis=1),
     lambda x: onp.delete(x, 2, axis=1)),
    ("insert",
     lambda m, x: m.insert(m.array(x), 1, m.array(x[0]), axis=0),
     lambda x: onp.insert(x, 1, x[0], axis=0)),
    ("hsplit", lambda m, x: m.hsplit(m.array(x[:, :4]), 2)[1],
     lambda x: onp.hsplit(x[:, :4], 2)[1]),
    ("vsplit", lambda m, x: m.vsplit(m.array(x), 2)[0],
     lambda x: onp.vsplit(x, 2)[0]),
    ("compress",
     lambda m, x: m.compress(m.array([0, 1, 1, 0]), m.array(x), axis=0),
     lambda x: onp.compress([0, 1, 1, 0], x, axis=0)),
    ("extract",
     lambda m, x: m.extract(m.array(x) > 0, m.array(x)),
     lambda x: onp.extract(x > 0, x)),
    ("select",
     lambda m, x: m.select([m.array(x) > 1, m.array(x) < -1],
                           [m.array(x), -m.array(x)], 0.0),
     lambda x: onp.select([x > 1, x < -1], [x, -x], onp.float32(0.0))),
    ("choose",
     lambda m, x: m.choose(m.array(onp.array([0, 1, 1, 0, 1],
                                             onp.int32)),
                           [m.array(x[0]), m.array(x[1])]),
     lambda x: onp.choose(onp.array([0, 1, 1, 0, 1], onp.int32),
                          [x[0], x[1]])),
    ("piecewise",
     lambda m, x: m.piecewise(m.array(x), [m.array(x) < 0,
                                           m.array(x) >= 0],
                              [lambda v: -v, lambda v: v * 2]),
     lambda x: onp.piecewise(x, [x < 0, x >= 0],
                             [lambda v: -v, lambda v: v * 2])),
    ("trim_zeros",
     lambda m, x: m.trim_zeros(m.array(onp.array([0, 0, 1, 2, 0, 3, 0],
                                                 onp.float32))),
     lambda x: onp.trim_zeros(onp.array([0, 0, 1, 2, 0, 3, 0],
                                        onp.float32))),
    ("inner", lambda m, x: m.inner(m.array(x), m.array(x)),
     lambda x: onp.inner(x, x)),
    ("vdot", lambda m, x: m.vdot(m.array(x), m.array(x)),
     lambda x: onp.vdot(x, x)),
    ("convolve",
     lambda m, x: m.convolve(m.array(x[0]),
                             m.array(onp.array([1.0, 0.5, 0.25],
                                               onp.float32))),
     lambda x: onp.convolve(x[0], onp.array([1.0, 0.5, 0.25],
                                            onp.float32))),
    ("correlate",
     lambda m, x: m.correlate(m.array(x[0]), m.array(x[1]), mode="full"),
     lambda x: onp.correlate(x[0], x[1], mode="full")),
    ("sinc", lambda m, x: m.sinc(m.array(x)), lambda x: onp.sinc(x)),
    ("i0", lambda m, x: m.i0(m.array(x[0])), lambda x: onp.i0(x[0])),
    ("nextafter",
     lambda m, x: m.nextafter(m.array(x), m.array(x + 1.0)),
     lambda x: onp.nextafter(x, x + 1.0)),
    ("tril_indices",
     lambda m, x: m.tril_indices(4, 0, 5)[0],
     lambda x: onp.tril_indices(4, 0, 5)[0]),
    ("triu_indices",
     lambda m, x: m.triu_indices(4, 1, 5)[1],
     lambda x: onp.triu_indices(4, 1, 5)[1]),
    ("diag_indices",
     lambda m, x: m.diag_indices(4)[0],
     lambda x: onp.diag_indices(4)[0]),
    ("diagonal",
     lambda m, x: m.diagonal(m.array(x), offset=1, axis1=0, axis2=1),
     lambda x: onp.diagonal(x, offset=1, axis1=0, axis2=1)),
    ("angle", lambda m, x: m.angle(m.array(x)), lambda x: onp.angle(x)),
    ("real", lambda m, x: m.real(m.array(x)), lambda x: onp.real(x)),
    ("imag", lambda m, x: m.imag(m.array(x)), lambda x: onp.imag(x)),
    ("conj", lambda m, x: m.conj(m.array(x)), lambda x: onp.conj(x)),
    ("positive", lambda m, x: m.positive(m.array(x)),
     lambda x: onp.positive(x)),
    ("negative", lambda m, x: m.negative(m.array(x)),
     lambda x: onp.negative(x)),
    ("around", lambda m, x: m.around(m.array(x * 3), 1),
     lambda x: onp.around(x * 3, 1)),
    ("nancumsum", lambda m, x: m.nancumsum(m.array(_xnan()[:2]), axis=1),
     lambda x: onp.nancumsum(_xnan()[:2], axis=1)),
    ("nanprod", lambda m, x: m.nanprod(m.array(_xnan()[:2]), axis=0),
     lambda x: onp.nanprod(_xnan()[:2], axis=0)),
    ("nanargmax", lambda m, x: m.nanargmax(m.array(_xnan()[:2]), axis=1),
     lambda x: onp.nanargmax(_xnan()[:2], axis=1)),
    ("nanargmin", lambda m, x: m.nanargmin(m.array(_xnan()[:2]), axis=1),
     lambda x: onp.nanargmin(_xnan()[:2], axis=1)),
    ("nanmin", lambda m, x: m.nanmin(m.array(_xnan()[:2]), axis=0),
     lambda x: onp.nanmin(_xnan()[:2], axis=0)),
    ("nanvar", lambda m, x: m.nanvar(m.array(_xnan()[:2]), axis=1),
     lambda x: onp.nanvar(_xnan()[:2], axis=1)),
    ("nanmedian", lambda m, x: m.nanmedian(m.array(_xnan()[:2]), axis=1),
     lambda x: onp.nanmedian(_xnan()[:2], axis=1)),
    ("gradient", lambda m, x: m.gradient(m.array(x), axis=1),
     lambda x: onp.gradient(x, axis=1)),
    ("allclose",
     lambda m, x: m.allclose(m.array(x), m.array(x + 1e-7)),
     lambda x: onp.allclose(x, x + 1e-7)),
    ("array_equal",
     lambda m, x: m.array_equal(m.array(x), m.array(x)),
     lambda x: onp.array_equal(x, x)),
]


@pytest.mark.parametrize("case", EXT_FNS3, ids=[c[0] for c in EXT_FNS3])
def test_np_extended_surface_round3(case):
    name, mx_fn, onp_fn = case
    if not hasattr(np, name):
        pytest.skip(f"mx.np.{name} absent")
    x = _r((4, 5), 37)
    got = mx_fn(np, x)
    got = got.asnumpy() if hasattr(got, "asnumpy") else onp.asarray(got)
    want = onp.asarray(onp_fn(x))
    assert got.shape == want.shape, \
        f"{name}: shape {got.shape} vs numpy {want.shape}"
    if want.dtype.kind == "b":
        assert onp.dtype(got.dtype).kind == "b", \
            f"{name}: bool result came back as {got.dtype}"
        onp.testing.assert_array_equal(got, want)
    elif want.dtype.kind in "iu":
        assert onp.dtype(got.dtype).kind in "iu", \
            f"{name}: integer result came back as {got.dtype}"
        onp.testing.assert_array_equal(got, want)
    else:
        onp.testing.assert_allclose(onp.asarray(got, want.dtype), want,
                                    rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# delegated-surface parity extension round 4 (ISSUE 13 satellite): another
# ~32-function slice toward the ~250-function namespace — stacking/split
# helpers, integer bitwise/shift ops (int result dtypes asserted), nan/inf
# predicates, angle conversions, histogramming, index-grid constructors
# (indices/ravel_multi_index/unravel_index), *_like constructors, the
# predicate-reduction aliases (all/any/amax/amin), and take/rollaxis/
# broadcast_arrays — again the thin-jnp-delegation spots where axis
# conventions and result dtypes could silently diverge.
# ---------------------------------------------------------------------------

def _xi():
    return onp.array([[5, 3, 12, 6, 9], [2, 7, 1, 8, 4]], onp.int32)


EXT_FNS4 = [
    ("absolute", lambda m, x: m.absolute(m.array(x)),
     lambda x: onp.absolute(x)),
    ("all", lambda m, x: m.all(m.array(x) > -100, axis=0),
     lambda x: onp.all(x > -100, axis=0)),
    ("any", lambda m, x: m.any(m.array(x) > 1, axis=1),
     lambda x: onp.any(x > 1, axis=1)),
    ("amax", lambda m, x: m.amax(m.array(x), axis=1),
     lambda x: onp.amax(x, axis=1)),
    ("amin", lambda m, x: m.amin(m.array(x), axis=0),
     lambda x: onp.amin(x, axis=0)),
    ("atleast_1d", lambda m, x: m.atleast_1d(m.array(x[0, 0])),
     lambda x: onp.atleast_1d(onp.float32(x[0, 0]))),
    ("atleast_3d", lambda m, x: m.atleast_3d(m.array(x)),
     lambda x: onp.atleast_3d(x)),
    ("bitwise_and", lambda m, x: m.bitwise_and(m.array(_xi()),
                                               m.array(_xi() + 1)),
     lambda x: onp.bitwise_and(_xi(), _xi() + 1)),
    ("bitwise_or", lambda m, x: m.bitwise_or(m.array(_xi()),
                                             m.array(_xi() + 1)),
     lambda x: onp.bitwise_or(_xi(), _xi() + 1)),
    ("bitwise_xor", lambda m, x: m.bitwise_xor(m.array(_xi()),
                                               m.array(_xi() + 1)),
     lambda x: onp.bitwise_xor(_xi(), _xi() + 1)),
    ("invert", lambda m, x: m.invert(m.array(_xi())),
     lambda x: onp.invert(_xi())),
    ("left_shift", lambda m, x: m.left_shift(m.array(_xi()), 2),
     lambda x: onp.left_shift(_xi(), 2)),
    ("right_shift", lambda m, x: m.right_shift(m.array(_xi()), 1),
     lambda x: onp.right_shift(_xi(), 1)),
    ("broadcast_arrays",
     lambda m, x: m.broadcast_arrays(m.array(x[:1]), m.array(x))[0],
     lambda x: onp.broadcast_arrays(x[:1], x)[0]),
    ("conjugate", lambda m, x: m.conjugate(m.array(x)),
     lambda x: onp.conjugate(x)),
    ("copy", lambda m, x: m.copy(m.array(x)), lambda x: onp.copy(x)),
    ("deg2rad", lambda m, x: m.deg2rad(m.array(x * 90)),
     lambda x: onp.deg2rad(x * 90)),
    ("rad2deg", lambda m, x: m.rad2deg(m.array(x)),
     lambda x: onp.rad2deg(x)),
    ("dsplit", lambda m, x: m.dsplit(m.array(x.reshape(2, 5, 2)), 2)[1],
     lambda x: onp.dsplit(x.reshape(2, 5, 2), 2)[1]),
    ("fix", lambda m, x: m.fix(m.array(x * 3)),
     lambda x: onp.fix(x * 3)),
    ("full_like", lambda m, x: m.full_like(m.array(x), 2.5),
     lambda x: onp.full_like(x, 2.5)),
    ("ones_like", lambda m, x: m.ones_like(m.array(_xi())),
     lambda x: onp.ones_like(_xi())),
    ("histogram",
     lambda m, x: m.histogram(m.array(x), bins=5,
                              range=(-3.0, 3.0))[0],
     lambda x: onp.histogram(x, bins=5, range=(-3.0, 3.0))[0]),
    ("hstack",
     lambda m, x: m.hstack((m.array(x), m.array(x[:, :2]))),
     lambda x: onp.hstack((x, x[:, :2]))),
    ("vstack",
     lambda m, x: m.vstack((m.array(x), m.array(x[:1]))),
     lambda x: onp.vstack((x, x[:1]))),
    ("indices", lambda m, x: m.indices((3, 4))[1],
     lambda x: onp.indices((3, 4))[1]),
    ("ravel_multi_index",
     lambda m, x: m.ravel_multi_index(
         (m.array(onp.array([0, 1, 2], onp.int32)),
          m.array(onp.array([3, 0, 4], onp.int32))), (4, 5)),
     lambda x: onp.ravel_multi_index(
         (onp.array([0, 1, 2]), onp.array([3, 0, 4])), (4, 5))),
    ("unravel_index",
     lambda m, x: m.unravel_index(
         m.array(onp.array([5, 11, 19], onp.int32)), (4, 5))[1],
     lambda x: onp.unravel_index(onp.array([5, 11, 19]), (4, 5))[1]),
    ("iscomplex", lambda m, x: m.iscomplex(m.array(x)),
     lambda x: onp.iscomplex(x)),
    ("isreal", lambda m, x: m.isreal(m.array(x)),
     lambda x: onp.isreal(x)),
    ("isneginf",
     lambda m, x: m.isneginf(m.array(
         onp.array([-onp.inf, 1.0, onp.inf], onp.float32))),
     lambda x: onp.isneginf(onp.array([-onp.inf, 1.0, onp.inf],
                                      onp.float32))),
    ("isposinf",
     lambda m, x: m.isposinf(m.array(
         onp.array([-onp.inf, 1.0, onp.inf], onp.float32))),
     lambda x: onp.isposinf(onp.array([-onp.inf, 1.0, onp.inf],
                                      onp.float32))),
    ("logaddexp2",
     lambda m, x: m.logaddexp2(m.array(x), m.array(x + 1.0)),
     lambda x: onp.logaddexp2(x, x + 1.0)),
    ("nancumprod",
     lambda m, x: m.nancumprod(m.array(_xnan()[:2] * 0.5), axis=1),
     lambda x: onp.nancumprod(_xnan()[:2] * 0.5, axis=1)),
    ("rollaxis", lambda m, x: m.rollaxis(m.array(x), 1, 0),
     lambda x: onp.rollaxis(x, 1, 0)),
    ("take",
     lambda m, x: m.take(m.array(x),
                         m.array(onp.array([3, 0, 2], onp.int32)),
                         axis=1),
     lambda x: onp.take(x, onp.array([3, 0, 2]), axis=1)),
]


@pytest.mark.parametrize("case", EXT_FNS4, ids=[c[0] for c in EXT_FNS4])
def test_np_extended_surface_round4(case):
    name, mx_fn, onp_fn = case
    if not hasattr(np, name):
        pytest.skip(f"mx.np.{name} absent")
    x = _r((4, 5), 41)
    got = mx_fn(np, x)
    got = got.asnumpy() if hasattr(got, "asnumpy") else onp.asarray(got)
    want = onp.asarray(onp_fn(x))
    assert got.shape == want.shape, \
        f"{name}: shape {got.shape} vs numpy {want.shape}"
    if want.dtype.kind == "b":
        assert onp.dtype(got.dtype).kind == "b", \
            f"{name}: bool result came back as {got.dtype}"
        onp.testing.assert_array_equal(got, want)
    elif want.dtype.kind in "iu":
        assert onp.dtype(got.dtype).kind in "iu", \
            f"{name}: integer result came back as {got.dtype}"
        onp.testing.assert_array_equal(got, want)
    else:
        onp.testing.assert_allclose(onp.asarray(got, want.dtype), want,
                                    rtol=2e-5, atol=2e-6)


def test_np_dtype_introspection_helpers():
    """result_type / promote_types / can_cast answer with the x64-less
    lattice where it AGREES with numpy (the divergent int32+f32 case is
    pinned by test_np_dtype_promotion)."""
    assert onp.dtype(np.result_type("float32", "float32")) == onp.float32
    assert onp.dtype(np.result_type("int32", "int8")) == onp.int32
    assert onp.dtype(np.promote_types("float32", "float64")) == onp.float64
    assert bool(np.can_cast("int32", "int64"))
    assert not bool(np.can_cast("float64", "int32"))


def test_npx_set_np_toggles():
    mx.npx.set_np()
    try:
        from mxnet_tpu.util import is_np_array
        assert is_np_array()
    finally:
        mx.npx.reset_np()
    from mxnet_tpu.util import is_np_array
    assert not is_np_array()


# ---------------------------------------------------------------------------
# delegated-surface parity extension round 5 (ISSUE 14 satellite): the
# ~38-function slice that closes most of the remaining shared-name gap —
# the comparison ufuncs (bool result dtypes asserted), the reduction
# core (sum/mean/prod/std/var/max/min + arg/cum forms with negative
# axes), constructors (arange/full/identity/ones/zeros/*_like incl.
# value+dtype), binary float helpers (copysign/hypot/logaddexp/
# true_divide), histogram2d, trapezoid integration, and the ndim/shape/
# size introspection helpers — again the thin-jnp-delegation spots where
# result dtypes and axis conventions could silently diverge.
# ---------------------------------------------------------------------------

EXT_FNS5 = [
    ("arange", lambda m, x: m.arange(2.0, 8.0, 1.5),
     lambda x: onp.arange(2.0, 8.0, 1.5)),
    ("arccosh", lambda m, x: m.arccosh(m.array(onp.abs(x) + 1.5)),
     lambda x: onp.arccosh(onp.abs(x) + 1.5)),
    ("argmax", lambda m, x: m.argmax(m.array(x), axis=-1),
     lambda x: onp.argmax(x, axis=-1)),
    ("argmin", lambda m, x: m.argmin(m.array(x), axis=0),
     lambda x: onp.argmin(x, axis=0)),
    ("array", lambda m, x: m.array(x), lambda x: onp.array(x)),
    ("asarray", lambda m, x: m.asarray(x), lambda x: onp.asarray(x)),
    ("ascontiguousarray", lambda m, x: m.ascontiguousarray(m.array(x).T),
     lambda x: onp.ascontiguousarray(x.T)),
    ("bitwise_not", lambda m, x: m.bitwise_not(m.array(_xi())),
     lambda x: onp.bitwise_not(_xi())),
    ("copysign", lambda m, x: m.copysign(m.array(x), m.array(-x)),
     lambda x: onp.copysign(x, -x)),
    ("cumprod", lambda m, x: m.cumprod(m.array(x * 0.5), axis=1),
     lambda x: onp.cumprod(x * 0.5, axis=1)),
    ("cumsum", lambda m, x: m.cumsum(m.array(x), axis=-1),
     lambda x: onp.cumsum(x, axis=-1)),
    ("equal", lambda m, x: m.equal(m.array(_xi()), m.array(_xi())),
     lambda x: onp.equal(_xi(), _xi())),
    ("not_equal",
     lambda m, x: m.not_equal(m.array(_xi()), m.array(_xi() * 0 + 5)),
     lambda x: onp.not_equal(_xi(), _xi() * 0 + 5)),
    ("greater", lambda m, x: m.greater(m.array(x), 0.0),
     lambda x: onp.greater(x, 0.0)),
    ("greater_equal",
     lambda m, x: m.greater_equal(m.array(x), m.array(x[:1])),
     lambda x: onp.greater_equal(x, x[:1])),
    ("less", lambda m, x: m.less(m.array(x), 0.5),
     lambda x: onp.less(x, 0.5)),
    ("less_equal", lambda m, x: m.less_equal(m.array(x), m.array(x)),
     lambda x: onp.less_equal(x, x)),
    ("full", lambda m, x: m.full((3, 4), 2.5),
     lambda x: onp.full((3, 4), 2.5)),
    ("histogram2d",
     lambda m, x: m.histogram2d(
         m.array(x.ravel()), m.array((x * 2).ravel()), bins=4,
         range=((-3.0, 3.0), (-6.0, 6.0)))[0],
     lambda x: onp.histogram2d(
         x.ravel(), (x * 2).ravel(), bins=4,
         range=((-3.0, 3.0), (-6.0, 6.0)))[0]),
    ("hypot", lambda m, x: m.hypot(m.array(x), m.array(x + 1.0)),
     lambda x: onp.hypot(x, x + 1.0)),
    ("identity", lambda m, x: m.identity(5),
     lambda x: onp.identity(5, dtype=onp.float32)),
    ("logaddexp",
     lambda m, x: m.logaddexp(m.array(x), m.array(x - 1.0)),
     lambda x: onp.logaddexp(x, x - 1.0)),
    ("max", lambda m, x: m.max(m.array(x), axis=1),
     lambda x: onp.max(x, axis=1)),
    ("min", lambda m, x: m.min(m.array(x), axis=-1, keepdims=True),
     lambda x: onp.min(x, axis=-1, keepdims=True)),
    ("mean", lambda m, x: m.mean(m.array(x), axis=0),
     lambda x: onp.mean(x, axis=0)),
    ("sum", lambda m, x: m.sum(m.array(x), axis=(0, 1)),
     lambda x: onp.sum(x, axis=(0, 1))),
    ("prod", lambda m, x: m.prod(m.array(x * 0.5 + 1.0), axis=1),
     lambda x: onp.prod(x * 0.5 + 1.0, axis=1)),
    ("std", lambda m, x: m.std(m.array(x), axis=1),
     lambda x: onp.std(x, axis=1)),
    ("var", lambda m, x: m.var(m.array(x), axis=0),
     lambda x: onp.var(x, axis=0)),
    ("ndim", lambda m, x: onp.int64(m.ndim(m.array(x))),
     lambda x: onp.int64(onp.ndim(x))),
    ("shape", lambda m, x: onp.array(m.shape(m.array(x))),
     lambda x: onp.array(onp.shape(x))),
    ("size", lambda m, x: onp.int64(m.size(m.array(x))),
     lambda x: onp.int64(onp.size(x))),
    ("ones", lambda m, x: m.ones((2, 3)),
     lambda x: onp.ones((2, 3), onp.float32)),
    ("zeros", lambda m, x: m.zeros((2, 3)),
     lambda x: onp.zeros((2, 3), onp.float32)),
    ("zeros_like", lambda m, x: m.zeros_like(m.array(_xi())),
     lambda x: onp.zeros_like(_xi())),
    ("round", lambda m, x: m.round(m.array(x * 3), 1),
     lambda x: onp.round(x * 3, 1)),
    ("true_divide",
     lambda m, x: m.true_divide(m.array(_xi()), m.array(_xi() + 1)),
     lambda x: onp.true_divide(_xi(), _xi() + 1)),
    ("trapezoid",
     lambda m, x: m.trapezoid(m.array(x), dx=0.5, axis=1),
     lambda x: getattr(onp, "trapezoid", getattr(onp, "trapz", None))(
         x, dx=0.5, axis=1)),
]


# ---------------------------------------------------------------------------
# delegated-surface parity extension round 6 (ISSUE 15 satellite): the
# ~50-function slice that closes the set-operation / window-function /
# polynomial / bit-packing families plus the numpy-2 array-API aliases
# (concat, permute_dims, matrix_transpose, vecdot) and the functional
# constructors (fromfunction, apply_along_axis/over_axes) — thin jnp
# delegation where result dtypes (bool/int asserts below), tuple-of-array
# returns (divmod/frexp/modf/ix_/indices-from), python-scalar returns
# (isscalar, broadcast_shapes) and CALLBACK arguments (mask_indices takes
# a mask_func — the delegated mx.np.triu returning NDArray into jnp was
# this round's delegation catch, now unwrapped host-side) could silently
# diverge.
# ---------------------------------------------------------------------------

EXT_FNS6 = [
    ("apply_along_axis",
     lambda m, x: m.apply_along_axis(lambda v: v.sum(), 1, m.array(x)),
     lambda x: onp.apply_along_axis(lambda v: v.sum(), 1, x)),
    ("apply_over_axes",
     lambda m, x: m.apply_over_axes(
         lambda a, ax: a.sum(ax, keepdims=True), m.array(x), [0]),
     lambda x: onp.apply_over_axes(
         lambda a, ax: a.sum(ax, keepdims=True), x, [0])),
    ("argpartition",
     lambda m, x: m.sort(m.argpartition(m.array(x[0]), 2)[:3]),
     lambda x: onp.sort(onp.argpartition(x[0], 2)[:3])),
    ("array_equiv", lambda m, x: m.array_equiv(m.array(x), m.array(x)),
     lambda x: onp.array_equiv(x, x)),
    ("bartlett", lambda m, x: m.bartlett(7), lambda x: onp.bartlett(7)),
    ("blackman", lambda m, x: m.blackman(7), lambda x: onp.blackman(7)),
    ("hamming", lambda m, x: m.hamming(7), lambda x: onp.hamming(7)),
    ("hanning", lambda m, x: m.hanning(7), lambda x: onp.hanning(7)),
    ("kaiser", lambda m, x: m.kaiser(7, 8.6),
     lambda x: onp.kaiser(7, 8.6)),
    ("broadcast_shapes",
     lambda m, x: onp.array(m.broadcast_shapes((3, 1), (1, 4))),
     lambda x: onp.array(onp.broadcast_shapes((3, 1), (1, 4)))),
    ("concat", lambda m, x: m.concat([m.array(x), m.array(x)]),
     lambda x: onp.concatenate([x, x])),
    ("diagflat", lambda m, x: m.diagflat(m.array(x[0, :3])),
     lambda x: onp.diagflat(x[0, :3])),
    ("diag_indices_from",
     lambda m, x: m.diag_indices_from(m.array(x[:4, :4]))[0],
     lambda x: onp.diag_indices_from(x[:4, :4])[0]),
    ("divmod", lambda m, x: m.divmod(m.array(_xi()), 3)[1],
     lambda x: onp.divmod(_xi(), 3)[1]),
    ("frexp", lambda m, x: m.frexp(m.array(x))[0],
     lambda x: onp.frexp(x)[0]),
    ("fromfunction",
     lambda m, x: m.fromfunction(lambda i, j: i + j, (3, 3)),
     lambda x: onp.fromfunction(lambda i, j: i + j, (3, 3))),
    ("geomspace", lambda m, x: m.geomspace(1.0, 64.0, 7),
     lambda x: onp.geomspace(1.0, 64.0, 7)),
    ("histogram_bin_edges",
     lambda m, x: m.histogram_bin_edges(m.array(x.ravel()), bins=5),
     lambda x: onp.histogram_bin_edges(x.ravel(), bins=5)),
    ("histogramdd",
     lambda m, x: m.histogramdd(m.array(x[:, :2]), bins=3)[0],
     lambda x: onp.histogramdd(x[:, :2], bins=3)[0]),
    ("intersect1d",
     lambda m, x: m.intersect1d(m.array(_xi().ravel()),
                                m.array(_xi().ravel()[:5])),
     lambda x: onp.intersect1d(_xi().ravel(), _xi().ravel()[:5])),
    ("isin",
     lambda m, x: m.isin(m.array(_xi()),
                         m.array(onp.array([1, 2], onp.int32))),
     lambda x: onp.isin(_xi(), onp.array([1, 2]))),
    ("iscomplexobj", lambda m, x: m.iscomplexobj(m.array(x)),
     lambda x: onp.iscomplexobj(x)),
    ("isrealobj", lambda m, x: m.isrealobj(m.array(x)),
     lambda x: onp.isrealobj(x)),
    ("isscalar", lambda m, x: m.isscalar(3.0),
     lambda x: onp.isscalar(3.0)),
    ("ix_",
     lambda m, x: m.ix_(m.array(onp.array([0, 2])),
                        m.array(onp.array([1, 3])))[0],
     lambda x: onp.ix_(onp.array([0, 2]), onp.array([1, 3]))[0]),
    ("lexsort", lambda m, x: m.lexsort((m.array(x[0]), m.array(x[1]))),
     lambda x: onp.lexsort((x[0], x[1]))),
    ("mask_indices", lambda m, x: m.mask_indices(3, m.triu)[0],
     lambda x: onp.mask_indices(3, onp.triu)[0]),
    ("matrix_transpose", lambda m, x: m.matrix_transpose(m.array(x)),
     lambda x: onp.swapaxes(x, -1, -2)),
    ("modf", lambda m, x: m.modf(m.array(x))[0],
     lambda x: onp.modf(x)[0]),
    ("nanpercentile", lambda m, x: m.nanpercentile(m.array(x), 40.0),
     lambda x: onp.nanpercentile(x, 40.0)),
    ("nanquantile", lambda m, x: m.nanquantile(m.array(x), 0.4),
     lambda x: onp.nanquantile(x, 0.4)),
    ("packbits",
     lambda m, x: m.packbits(m.array((_xi() % 2).astype(onp.uint8))),
     lambda x: onp.packbits((_xi() % 2).astype(onp.uint8))),
    ("unpackbits",
     lambda m, x: m.unpackbits(m.array(onp.array([7, 200], onp.uint8))),
     lambda x: onp.unpackbits(onp.array([7, 200], onp.uint8))),
    ("partition", lambda m, x: m.partition(m.array(x[0]), 2)[2],
     lambda x: onp.partition(x[0], 2)[2]),
    ("permute_dims", lambda m, x: m.permute_dims(m.array(x), (1, 0)),
     lambda x: onp.transpose(x, (1, 0))),
    ("polyadd",
     lambda m, x: m.polyadd(m.array(x[0, :3]), m.array(x[1, :3])),
     lambda x: onp.polyadd(x[0, :3], x[1, :3])),
    ("polyder", lambda m, x: m.polyder(m.array(x[0, :4])),
     lambda x: onp.polyder(x[0, :4])),
    ("polyint", lambda m, x: m.polyint(m.array(x[0, :4])),
     lambda x: onp.polyint(x[0, :4])),
    ("polymul",
     lambda m, x: m.polymul(m.array(x[0, :3]), m.array(x[1, :3])),
     lambda x: onp.polymul(x[0, :3], x[1, :3])),
    ("polysub",
     lambda m, x: m.polysub(m.array(x[0, :3]), m.array(x[1, :3])),
     lambda x: onp.polysub(x[0, :3], x[1, :3])),
    ("polyval",
     lambda m, x: m.polyval(m.array(x[0, :3]), m.array(x[1])),
     lambda x: onp.polyval(x[0, :3], x[1])),
    ("resize", lambda m, x: m.resize(m.array(x), (2, 3)),
     lambda x: onp.resize(x, (2, 3))),
    ("setdiff1d",
     lambda m, x: m.setdiff1d(m.array(_xi().ravel()),
                              m.array(onp.array([0, 1], onp.int32))),
     lambda x: onp.setdiff1d(_xi().ravel(), onp.array([0, 1]))),
    ("setxor1d",
     lambda m, x: m.setxor1d(m.array(onp.array([1, 2, 3])),
                             m.array(onp.array([2, 3, 4]))),
     lambda x: onp.setxor1d(onp.array([1, 2, 3]),
                            onp.array([2, 3, 4]))),
    ("sort_complex",
     lambda m, x: m.sort_complex(m.array(onp.array([3.0, 1.0, 2.0]))),
     lambda x: onp.sort_complex(onp.array([3.0, 1.0, 2.0]))),
    ("spacing", lambda m, x: m.spacing(m.array(x)),
     lambda x: onp.spacing(x)),
    ("tril_indices_from",
     lambda m, x: m.tril_indices_from(m.array(x[:4, :4]))[0],
     lambda x: onp.tril_indices_from(x[:4, :4])[0]),
    ("triu_indices_from",
     lambda m, x: m.triu_indices_from(m.array(x[:4, :4]))[1],
     lambda x: onp.triu_indices_from(x[:4, :4])[1]),
    ("union1d",
     lambda m, x: m.union1d(m.array(onp.array([1, 2, 3])),
                            m.array(onp.array([2, 5]))),
     lambda x: onp.union1d(onp.array([1, 2, 3]), onp.array([2, 5]))),
    ("unwrap", lambda m, x: m.unwrap(m.array(x[0] * 3)),
     lambda x: onp.unwrap(x[0] * 3)),
    ("vander", lambda m, x: m.vander(m.array(x[0, :3]), 3),
     lambda x: onp.vander(x[0, :3], 3)),
    ("vecdot", lambda m, x: m.vecdot(m.array(x), m.array(x)),
     lambda x: (x * x).sum(-1)),
]


@pytest.mark.parametrize("case", EXT_FNS6, ids=[c[0] for c in EXT_FNS6])
def test_np_extended_surface_round6(case):
    name, mx_fn, onp_fn = case
    if not hasattr(np, name):
        pytest.skip(f"mx.np.{name} absent")
    x = _r((4, 5), 61)
    got = mx_fn(np, x)
    got = got.asnumpy() if hasattr(got, "asnumpy") else onp.asarray(got)
    want = onp.asarray(onp_fn(x))
    assert got.shape == want.shape, \
        f"{name}: shape {got.shape} vs numpy {want.shape}"
    if want.dtype.kind == "b":
        assert onp.dtype(got.dtype).kind == "b", \
            f"{name}: bool result came back as {got.dtype}"
        onp.testing.assert_array_equal(got, want)
    elif want.dtype.kind in "iu":
        assert onp.dtype(got.dtype).kind in "iu", \
            f"{name}: integer result came back as {got.dtype}"
        onp.testing.assert_array_equal(got, want)
    else:
        onp.testing.assert_allclose(onp.asarray(got, want.dtype), want,
                                    rtol=2e-5, atol=2e-6)


@pytest.mark.parametrize("case", EXT_FNS5, ids=[c[0] for c in EXT_FNS5])
def test_np_extended_surface_round5(case):
    name, mx_fn, onp_fn = case
    if not hasattr(np, name):
        pytest.skip(f"mx.np.{name} absent")
    x = _r((4, 5), 51)
    got = mx_fn(np, x)
    got = got.asnumpy() if hasattr(got, "asnumpy") else onp.asarray(got)
    want = onp.asarray(onp_fn(x))
    assert got.shape == want.shape, \
        f"{name}: shape {got.shape} vs numpy {want.shape}"
    if want.dtype.kind == "b":
        assert onp.dtype(got.dtype).kind == "b", \
            f"{name}: bool result came back as {got.dtype}"
        onp.testing.assert_array_equal(got, want)
    elif want.dtype.kind in "iu":
        assert onp.dtype(got.dtype).kind in "iu", \
            f"{name}: integer result came back as {got.dtype}"
        onp.testing.assert_array_equal(got, want)
    else:
        onp.testing.assert_allclose(onp.asarray(got, want.dtype), want,
                                    rtol=2e-5, atol=2e-6)


# -- round 7 (ISSUE 16): array-API aliases, polynomial solvers, unique_*
# quartet, popcount/shift family, block assembly, and the put/place/
# fill_diagonal copy-returning shims (documented divergence: jax arrays
# are immutable, numpy mutates in place).

def _put_ref(x):
    y = x.copy()
    onp.put(y, [0, 2], [9.0, 8.0])
    return y


def _place_ref(x):
    y = x.copy()
    onp.place(y, x > 0, [5.0])
    return y


def _fill_diag_ref(x):
    y = x[:4, :4].copy()
    onp.fill_diagonal(y, 7.0)
    return y


def _popcount_ref(x):
    xi = _xi()
    return onp.array([[bin(int(v)).count("1") for v in row] for row in xi],
                     onp.int32)


EXT_FNS7 = [
    ("acos", lambda m, x: m.acos(m.array(onp.tanh(x))),
     lambda x: onp.arccos(onp.tanh(x))),
    ("acosh", lambda m, x: m.acosh(m.array(1.0 + x * x)),
     lambda x: onp.arccosh(1.0 + x * x)),
    ("asin", lambda m, x: m.asin(m.array(onp.tanh(x))),
     lambda x: onp.arcsin(onp.tanh(x))),
    ("asinh", lambda m, x: m.asinh(m.array(x)),
     lambda x: onp.arcsinh(x)),
    ("atan", lambda m, x: m.atan(m.array(x)), lambda x: onp.arctan(x)),
    ("atan2", lambda m, x: m.atan2(m.array(x), m.array(x + 1.5)),
     lambda x: onp.arctan2(x, x + 1.5)),
    ("atanh", lambda m, x: m.atanh(m.array(onp.tanh(x) * 0.9)),
     lambda x: onp.arctanh(onp.tanh(x) * 0.9)),
    ("pow", lambda m, x: m.pow(m.array(onp.abs(x) + 0.5), 2),
     lambda x: onp.power(onp.abs(x) + 0.5, 2)),
    ("bitwise_count", lambda m, x: m.bitwise_count(m.array(_xi())),
     _popcount_ref),
    ("bitwise_invert", lambda m, x: m.bitwise_invert(m.array(_xi())),
     lambda x: onp.invert(_xi())),
    ("bitwise_left_shift",
     lambda m, x: m.bitwise_left_shift(m.array(_xi()), 2),
     lambda x: onp.left_shift(_xi(), 2)),
    ("bitwise_right_shift",
     lambda m, x: m.bitwise_right_shift(m.array(_xi()), 1),
     lambda x: onp.right_shift(_xi(), 1)),
    ("block", lambda m, x: m.block([[m.array(x)], [m.array(x)]]),
     lambda x: onp.block([[x], [x]])),
    ("cumulative_sum",
     lambda m, x: m.cumulative_sum(m.array(x), axis=1),
     lambda x: onp.cumsum(x, axis=1)),
    ("cumulative_prod",
     lambda m, x: m.cumulative_prod(m.array(x), axis=1),
     lambda x: onp.cumprod(x, axis=1)),
    ("astype", lambda m, x: m.astype(m.array(x * 10), "int32"),
     lambda x: (x * 10).astype(onp.int32)),
    ("fmod", lambda m, x: m.fmod(m.array(_xi()), 3),
     lambda x: onp.fmod(_xi(), 3)),
    ("isdtype",
     lambda m, x: onp.array(m.isdtype(onp.dtype("float32"),
                                      "real floating")),
     lambda x: onp.array(True)),
    ("poly", lambda m, x: m.poly(m.array(x[0, :3])),
     lambda x: onp.poly(x[0, :3])),
    ("polydiv",
     lambda m, x: m.polydiv(m.array(onp.array([1.0, 3.0, 2.0])),
                            m.array(onp.array([1.0, 1.0])))[0],
     lambda x: onp.polydiv(onp.array([1.0, 3.0, 2.0]),
                           onp.array([1.0, 1.0]))[0]),
    ("polyfit",
     lambda m, x: m.polyfit(m.array(onp.arange(5.0)), m.array(x[1]), 1),
     lambda x: onp.polyfit(onp.arange(5.0), x[1], 1)),
    ("roots",
     lambda m, x: m.sort(m.abs(m.roots(
         m.array(onp.array([1.0, -3.0, 2.0]))))),
     lambda x: onp.sort(onp.abs(onp.roots(onp.array([1.0, -3.0, 2.0]))))),
    ("unique_all", lambda m, x: m.unique_all(m.array(_xi()))[0],
     lambda x: onp.unique(_xi())),
    ("unique_counts", lambda m, x: m.unique_counts(m.array(_xi()))[1],
     lambda x: onp.unique(_xi(), return_counts=True)[1]),
    ("unique_inverse", lambda m, x: m.unique_inverse(m.array(_xi()))[1],
     lambda x: onp.unique(_xi(), return_inverse=True)[1].reshape(
         _xi().shape)),
    ("unique_values", lambda m, x: m.unique_values(m.array(_xi())),
     lambda x: onp.unique(_xi())),
    ("unstack", lambda m, x: m.unstack(m.array(x))[1],
     lambda x: x[1]),
    ("put",
     lambda m, x: m.put(m.array(x), m.array(onp.array([0, 2])),
                        m.array(onp.array([9.0, 8.0], onp.float32))),
     _put_ref),
    ("place",
     lambda m, x: m.place(m.array(x), m.array(x > 0),
                          m.array(onp.array([5.0], onp.float32))),
     _place_ref),
    ("fill_diagonal",
     lambda m, x: m.fill_diagonal(m.array(x[:4, :4]), 7.0),
     _fill_diag_ref),
]


@pytest.mark.parametrize("case", EXT_FNS7, ids=[c[0] for c in EXT_FNS7])
def test_np_extended_surface_round7(case):
    name, mx_fn, onp_fn = case
    if not hasattr(np, name):
        pytest.skip(f"mx.np.{name} absent")
    x = _r((4, 5), 71)
    got = mx_fn(np, x)
    got = got.asnumpy() if hasattr(got, "asnumpy") else onp.asarray(got)
    want = onp.asarray(onp_fn(x))
    assert got.shape == want.shape, \
        f"{name}: shape {got.shape} vs numpy {want.shape}"
    if want.dtype.kind == "b":
        assert onp.dtype(got.dtype).kind == "b", \
            f"{name}: bool result came back as {got.dtype}"
        onp.testing.assert_array_equal(got, want)
    elif want.dtype.kind in "iu":
        assert onp.dtype(got.dtype).kind in "iu", \
            f"{name}: integer result came back as {got.dtype}"
        onp.testing.assert_array_equal(got, want)
    else:
        onp.testing.assert_allclose(onp.asarray(got, want.dtype), want,
                                    rtol=2e-5, atol=2e-6)


# -- round 8 (ISSUE 19): the np.fft subnamespace, the remaining linalg
# array-API members (diagonal/matrix_transpose/tensordot/vecdot), the
# host-data constructors (frombuffer/fromiter), vectorize, and the
# host-returning helpers (array_repr/array_str/einsum_path/issubdtype/
# iterable).  Dotted names resolve through subnamespaces; fft cases
# compare magnitudes so the complex64-vs-complex128 width difference
# stays inside the float tolerance.

def _np_attr(m, dotted):
    for part in dotted.split("."):
        if not hasattr(m, part):
            return None
        m = getattr(m, part)
    return m


EXT_FNS8 = [
    ("fft.fft", lambda m, x: m.abs(m.fft.fft(m.array(x), axis=1)),
     lambda x: onp.abs(onp.fft.fft(x, axis=1))),
    ("fft.ifft", lambda m, x: m.abs(m.fft.ifft(m.array(x), axis=1)),
     lambda x: onp.abs(onp.fft.ifft(x, axis=1))),
    ("fft.rfft", lambda m, x: m.abs(m.fft.rfft(m.array(x), axis=1)),
     lambda x: onp.abs(onp.fft.rfft(x, axis=1))),
    ("fft.irfft", lambda m, x: m.fft.irfft(m.array(x), axis=1),
     lambda x: onp.fft.irfft(x, axis=1)),
    ("fft.fft2", lambda m, x: m.abs(m.fft.fft2(m.array(x))),
     lambda x: onp.abs(onp.fft.fft2(x))),
    ("fft.ifft2", lambda m, x: m.abs(m.fft.ifft2(m.array(x))),
     lambda x: onp.abs(onp.fft.ifft2(x))),
    ("fft.fftn", lambda m, x: m.abs(m.fft.fftn(m.array(x))),
     lambda x: onp.abs(onp.fft.fftn(x))),
    ("fft.ifftn", lambda m, x: m.abs(m.fft.ifftn(m.array(x))),
     lambda x: onp.abs(onp.fft.ifftn(x))),
    ("fft.rfft2", lambda m, x: m.abs(m.fft.rfft2(m.array(x))),
     lambda x: onp.abs(onp.fft.rfft2(x))),
    ("fft.irfft2", lambda m, x: m.fft.irfft2(m.array(x)),
     lambda x: onp.fft.irfft2(x)),
    ("fft.rfftn", lambda m, x: m.abs(m.fft.rfftn(m.array(x))),
     lambda x: onp.abs(onp.fft.rfftn(x))),
    ("fft.irfftn", lambda m, x: m.fft.irfftn(m.array(x)),
     lambda x: onp.fft.irfftn(x)),
    ("fft.hfft", lambda m, x: m.fft.hfft(m.array(x), axis=1),
     lambda x: onp.fft.hfft(x, axis=1)),
    ("fft.ihfft", lambda m, x: m.abs(m.fft.ihfft(m.array(x), axis=1)),
     lambda x: onp.abs(onp.fft.ihfft(x, axis=1))),
    ("fft.fftfreq", lambda m, x: m.fft.fftfreq(8, d=0.5),
     lambda x: onp.fft.fftfreq(8, d=0.5)),
    ("fft.rfftfreq", lambda m, x: m.fft.rfftfreq(8, d=0.5),
     lambda x: onp.fft.rfftfreq(8, d=0.5)),
    ("fft.fftshift", lambda m, x: m.fft.fftshift(m.array(x), axes=1),
     lambda x: onp.fft.fftshift(x, axes=1)),
    ("fft.ifftshift", lambda m, x: m.fft.ifftshift(m.array(x), axes=1),
     lambda x: onp.fft.ifftshift(x, axes=1)),
    ("linalg.diagonal",
     lambda m, x: m.linalg.diagonal(m.array(x[:4, :4])),
     lambda x: onp.linalg.diagonal(x[:4, :4])),
    ("linalg.matrix_transpose",
     lambda m, x: m.linalg.matrix_transpose(m.array(x)),
     lambda x: x.T),
    ("linalg.tensordot",
     lambda m, x: m.linalg.tensordot(m.array(x), m.array(x.T), axes=1),
     lambda x: onp.tensordot(x, x.T, axes=1)),
    ("linalg.vecdot",
     lambda m, x: m.linalg.vecdot(m.array(x), m.array(x + 1.0)),
     lambda x: onp.einsum("ij,ij->i", x, x + 1.0)),
    ("frombuffer",
     lambda m, x: m.frombuffer(x.tobytes(), dtype="float32"),
     lambda x: onp.frombuffer(x.tobytes(), dtype=onp.float32)),
    ("fromiter",
     lambda m, x: m.fromiter((float(i) for i in range(6)),
                             dtype="float32", count=6),
     lambda x: onp.fromiter((float(i) for i in range(6)),
                            dtype=onp.float32, count=6)),
    ("vectorize",
     lambda m, x: m.vectorize(lambda a, b: a * b + 1.0)(
         m.array(x), m.array(x)),
     lambda x: x * x + 1.0),
]


@pytest.mark.parametrize("case", EXT_FNS8, ids=[c[0] for c in EXT_FNS8])
def test_np_extended_surface_round8(case):
    name, mx_fn, onp_fn = case
    if _np_attr(np, name) is None:
        pytest.skip(f"mx.np.{name} absent")
    x = _r((4, 5), 81)
    got = mx_fn(np, x)
    got = got.asnumpy() if hasattr(got, "asnumpy") else onp.asarray(got)
    want = onp.asarray(onp_fn(x))
    assert got.shape == want.shape, \
        f"{name}: shape {got.shape} vs numpy {want.shape}"
    if want.dtype.kind == "b":
        assert onp.dtype(got.dtype).kind == "b", \
            f"{name}: bool result came back as {got.dtype}"
        onp.testing.assert_array_equal(got, want)
    elif want.dtype.kind in "iu":
        assert onp.dtype(got.dtype).kind in "iu", \
            f"{name}: integer result came back as {got.dtype}"
        onp.testing.assert_array_equal(got, want)
    else:
        onp.testing.assert_allclose(onp.asarray(got, want.dtype), want,
                                    rtol=2e-4, atol=2e-5)


def test_np_round8_host_helpers():
    """The string/bool-returning helpers stay host-side: they take an
    NDArray and hand back plain python values, never op outputs."""
    a = np.array(onp.arange(4.0, dtype=onp.float32))
    r = np.array_repr(a)
    s = np.array_str(a)
    assert isinstance(r, str) and "3." in r
    assert isinstance(s, str) and "3." in s
    assert np.iterable(a) is True
    assert np.iterable(3.0) is False
    assert np.issubdtype(onp.float32, onp.floating)
    assert not np.issubdtype(onp.int32, onp.floating)
    # (jnp's path omits numpy's "einsum_path" header element and hands
    # back opt_einsum's PathInfo object where numpy prints a string — the
    # contraction report lives in its str())
    path, info = np.einsum_path("ij,jk->ik", a.reshape(2, 2),
                                a.reshape(2, 2))
    assert isinstance(path, list)
    assert "Complete contraction" in str(info)
