"""The linear-attention ops (ops/linear_attention.py): the chunked gated
delta rule against the token-by-token recurrence it must equal, out and all
five gradients, at lengths that are and are not whole chunks and with
decays near 0 and near 1, in float32 and bfloat16, and with a chunk's keys
repeated or nearly parallel, writes at full strength and no decay (where a
sum of the system's powers cancels to nothing); the triangular solve inside
a chunk; the depthwise causal convolution."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu.ops.linear_attention import (_causal_conv1d,
                                            _gated_delta_rule,
                                            _unit_lower_inverse)


def recurrence(q, k, v, g, beta):
    """The definition, (B, L, H, D) float32, one position at a time."""
    b, _l, h, dk = q.shape

    def token(state, x):
        q, k, v, g, beta = x
        state = state * jnp.exp(g)[..., None, None]
        seen = jnp.einsum("bhkv,bhk->bhv", state, k)
        state = state + jnp.einsum("bhk,bhv->bhkv", k,
                                   beta[..., None] * (v - seen))
        return state, jnp.einsum("bhkv,bhk->bhv", state, q)

    xs = tuple(jnp.moveaxis(x.astype(jnp.float32), 1, 0)
               for x in (q, k, v, g, beta))
    _, out = jax.lax.scan(token, jnp.zeros((b, h, dk, v.shape[-1]),
                                           jnp.float32), xs)
    return jnp.moveaxis(out, 0, 1)


# log-decay a position: nearly none, strong (the state is all but gone in a
# few positions), and everything between
DECAYS = {"near_one": (-1e-3, -1e-5), "near_zero": (-8.0, -3.0),
          "mixed": (-3.0, 0.0)}


def _inputs(length, decay, dtype, seed=0, batch=2, heads=3, dk=16, dv=8):
    rs = np.random.RandomState(seed)
    q = rs.randn(batch, length, heads, dk)
    k = rs.randn(batch, length, heads, dk)
    q /= np.linalg.norm(q, axis=-1, keepdims=True) * dk ** 0.5
    k /= np.linalg.norm(k, axis=-1, keepdims=True)
    v = rs.randn(batch, length, heads, dv)
    g = rs.uniform(*DECAYS[decay], (batch, length, heads))
    beta = rs.uniform(0, 1, (batch, length, heads))
    wide = [jnp.asarray(x, jnp.float32).astype(dtype) for x in (q, k, v)]
    return wide + [jnp.asarray(g, jnp.float32), jnp.asarray(beta,
                                                            jnp.float32)]


def _worst(got, want):
    """Largest error as a share of the largest element."""
    want = np.asarray(want, np.float32)
    return float(np.abs(np.asarray(got, np.float32) - want).max()
                 / max(np.abs(want).max(), 1e-30))


def _chunked_loss(q, k, v, g, beta, weight):
    return (_gated_delta_rule(q, k, v, g, beta, chunk=64)
            .astype(jnp.float32) * weight).sum()


def _plain_loss(q, k, v, g, beta, weight):
    return (recurrence(q, k, v, g, beta) * weight).sum()


# one compile a (type, length): the decays are values, not shapes
_CHUNKED = jax.jit(lambda *a: _gated_delta_rule(*a, chunk=64))
_PLAIN = jax.jit(recurrence)
_CHUNKED_GRAD = jax.jit(jax.grad(_chunked_loss, argnums=(0, 1, 2, 3, 4)))
_PLAIN_GRAD = jax.jit(jax.grad(_plain_loss, argnums=(0, 1, 2, 3, 4)))


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("length", [64, 192, 100, 7],
                         ids=["one_chunk", "three_chunks", "ragged_100",
                              "shorter_than_a_chunk"])
@pytest.mark.parametrize("dtype, tol", [("float32", 3e-5),
                                        ("bfloat16", 2e-2)])
def test_chunked_rule_equals_the_recurrence_out_and_gradients(
        dtype, tol, length, decay):
    args = _inputs(length, decay, dtype)
    weight = jnp.asarray(np.random.RandomState(1).randn(
        2, length, 3, 8), jnp.float32)
    out = _CHUNKED(*args)
    assert out.dtype == args[2].dtype and out.shape == (2, length, 3, 8)
    assert _worst(out, _PLAIN(*args)) <= tol
    got = _CHUNKED_GRAD(*args, weight)
    want = _PLAIN_GRAD(*args, weight)
    for name, a, b in zip(("dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert np.isfinite(np.asarray(a, np.float32)).all(), name
        assert _worst(a, b) <= tol, name


def _parallel_keys(length, spread, seed=0):
    """Keys that all lie within ``spread`` of one direction a head, write
    strengths in 0.95-1, a position's decay within 1e-3 of 1: the entries
    under the chunk's diagonal are then all about 1, the case a trained
    model meets on a run of one token."""
    rs = np.random.RandomState(seed)
    args = _inputs(length, "near_one", "float32", seed=seed, batch=1,
                   heads=2)
    k = rs.randn(1, 1, 2, 16) + spread * rs.randn(1, length, 2, 16)
    args[1] = jnp.asarray(k / np.linalg.norm(k, axis=-1, keepdims=True),
                          jnp.float32)
    args[4] = jnp.asarray(rs.uniform(0.95, 1.0, (1, length, 2)),
                          jnp.float32)
    return args


@pytest.mark.parametrize("spread", [0.0, 0.05, 0.3],
                         ids=["repeated", "nearly_parallel", "loose"])
@pytest.mark.parametrize("length", [128, 100], ids=["two_chunks", "ragged"])
@pytest.mark.parametrize("dtype, tol", [("float32", 3e-5),
                                        ("bfloat16", 6e-2)])
def test_chunked_rule_with_parallel_keys_in_a_chunk(dtype, tol, length,
                                                    spread):
    args = _parallel_keys(length, spread)
    args[:3] = [x.astype(dtype) for x in args[:3]]
    exact = [x.astype(jnp.float32) for x in args[:3]] + args[3:]
    weight = jnp.asarray(np.random.RandomState(1).randn(
        1, length, 2, 8), jnp.float32)
    assert _worst(_CHUNKED(*args), _PLAIN(*exact)) <= tol
    got = _CHUNKED_GRAD(*args, weight)
    want = _PLAIN_GRAD(*exact, weight)
    for name, a, b in zip(("dq", "dk", "dv", "dg", "dbeta"), got, want):
        assert np.isfinite(np.asarray(a, np.float32)).all(), name
        # dg's largest element is small beside its cancelling terms when
        # the keys repeat: the recurrence's own is no better known, and
        # what bfloat16 rounds away is as large
        if name == "dg" and spread < 0.3:
            if dtype == "float32":
                assert _worst(a, b) <= 1e-3, name
            continue
        assert _worst(a, b) <= tol, name


def test_unit_lower_inverse_of_all_ones_under_the_diagonal():
    """``(I + L)^-1`` is 1 on the diagonal and -1 just under it; ``L^k``
    reaches 1e18 on the way through a sum of powers."""
    c = 64
    a = jnp.asarray(np.tril(np.ones((c, c)), -1), jnp.float32)
    want = np.eye(c) - np.eye(c, k=-1)
    np.testing.assert_allclose(np.asarray(_unit_lower_inverse(a)), want,
                               atol=1e-5)


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_the_chunk_is_an_implementation_detail(chunk):
    args = _inputs(48, "mixed", "float32", seed=3)
    want = recurrence(*args)
    assert _worst(_gated_delta_rule(*args, chunk=chunk), want) <= 3e-5


def test_decays_that_would_overflow_as_a_ratio_of_exponentials():
    """exp(G_i) * exp(-G_j) overflows float32 once a chunk's log-decays sum
    past 88; the ratio taken under the mask does not."""
    args = _inputs(64, "near_zero", "float32", seed=4)
    args[3] = jnp.full_like(args[3], -4.0)          # 64 x -4 = -256 a chunk
    out = _gated_delta_rule(*args, chunk=64)
    assert np.isfinite(np.asarray(out)).all()
    assert _worst(out, recurrence(*args)) <= 3e-5
    grads = jax.grad(lambda *a: _gated_delta_rule(*a, chunk=64).sum(),
                     argnums=(0, 1, 2, 3, 4))(*args)
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)


def test_unit_lower_inverse_is_the_inverse():
    rs = np.random.RandomState(0)
    for c in (1, 2, 5, 64):
        a = np.tril(rs.randn(3, c, c), -1).astype(np.float32)
        got = np.asarray(_unit_lower_inverse(jnp.asarray(a)))
        want = np.linalg.inv(np.eye(c) + a.astype(np.float64))
        np.testing.assert_allclose(got, want, rtol=2e-4,
                                   atol=2e-4 * np.abs(want).max())


def test_unit_lower_inverse_gradient_is_the_closed_form():
    """``-inv^T g inv^T``, against the gradient through a dense inverse."""
    rs = np.random.RandomState(1)
    a = jnp.asarray(np.tril(rs.randn(2, 16, 16), -1) * 0.3, jnp.float32)
    w = jnp.asarray(rs.randn(2, 16, 16), jnp.float32)
    got = jax.grad(lambda a: (_unit_lower_inverse(a) * w).sum())(a)
    want = jax.grad(lambda a: (jnp.linalg.inv(
        jnp.eye(16, dtype=jnp.float32) + a) * w).sum())(a)
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-4 * float(jnp.abs(want).max()))


def test_the_op_through_nd_records_on_the_tape():
    args = _inputs(70, "mixed", "float32", seed=5, batch=1)
    nds = [mx.nd.NDArray._from_data(a) for a in args]
    for a in nds:
        a.attach_grad()
    with mx.autograd.record():
        out = mx.nd.contrib.gated_delta_rule(*nds, chunk=64)
        loss = (out * out).sum()
    loss.backward()
    want = jax.grad(lambda *a: (recurrence(*a) ** 2).sum(),
                    argnums=(0, 1, 2, 3, 4))(*args)
    for a, b in zip(nds, want):
        assert _worst(a.grad.asnumpy(), b) <= 3e-5


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("taps", [1, 4])
def test_causal_conv_is_a_left_padded_depthwise_conv(dtype, taps):
    rs = np.random.RandomState(0)
    x = rs.randn(2, 11, 6).astype(np.float32)
    w = rs.randn(6, taps).astype(np.float32)
    want = np.zeros_like(x)
    for t in range(11):
        for j in range(taps):
            src = t - (taps - 1) + j
            if src >= 0:
                want[:, t] += w[:, j] * x[:, src]
    got = _causal_conv1d(jnp.asarray(x).astype(dtype),
                         jnp.asarray(w).astype(dtype))
    assert got.dtype == jnp.dtype(dtype)
    np.testing.assert_allclose(np.asarray(got, np.float32), want,
                               atol=1e-5 if dtype == "float32" else 6e-2)
    # position t sees nothing after t
    later = x.copy()
    later[:, 7:] += 1.0
    moved = np.asarray(_causal_conv1d(jnp.asarray(later), jnp.asarray(w)))
    np.testing.assert_array_equal(
        moved[:, :7], np.asarray(_causal_conv1d(jnp.asarray(x),
                                                jnp.asarray(w)))[:, :7])


def test_causal_conv_gradients():
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(1, 9, 4), jnp.float32)
    w = jnp.asarray(rs.randn(4, 4), jnp.float32)

    def by_hand(x, w):
        padded = jnp.pad(x, ((0, 0), (3, 0), (0, 0)))
        windows = jnp.stack([padded[:, j:j + 9] for j in range(4)], -1)
        return (jnp.einsum("blcj,cj->blc", windows, w) ** 2).sum()

    got = jax.grad(lambda x, w: (_causal_conv1d(x, w) ** 2).sum(),
                   argnums=(0, 1))(x, w)
    want = jax.grad(by_hand, argnums=(0, 1))(x, w)
    for a, b in zip(got, want):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
