"""``contrib.ssd_scan``, the Mamba-2 scan in chunks, against its definition:
the recurrence token by token, forward and the gradient of every input, in
float32 on the CPU; lengths that fill, cross and miss chunk boundaries, one
group of B and C for all heads and one a head, strong and weak decay; bf16
inputs; the region scope."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.ops import state_space


def recurrence(x, dt, A, B, C, D):
    """h_t = exp(dt_t A) h_{t-1} + dt_t x_t B_t^T, y_t = h_t C_t + D x_t a
    head, h (P, N) zero at the start; float32, every product at the
    highest precision."""
    b, l, h, p = x.shape
    g, n = B.shape[2:]
    heads = h // g
    Bh = jnp.repeat(B, heads, axis=2)       # group j serves heads j*r…
    Ch = jnp.repeat(C, heads, axis=2)

    def step(state, t):
        xt, dtt, bt, ct = t
        state = jnp.exp(dtt * A)[..., None, None] * state \
            + jnp.einsum("bhp,bhn->bhpn", xt * dtt[..., None], bt,
                         precision="highest")
        return state, jnp.einsum("bhpn,bhn->bhp", state, ct,
                                 precision="highest")

    _, ys = jax.lax.scan(step, jnp.zeros((b, h, p, n), jnp.float32),
                         tuple(jnp.moveaxis(t, 1, 0) for t in
                               (x, dt, Bh, Ch)))
    return jnp.moveaxis(ys, 0, 1) + D[:, None] * x


def _inputs(seed, b, l, h, p, g, n, decay):
    """x, B, C of the widths of a Mamba-2 mixer's convolution output;
    steps around 0.1 and ``A`` such that a position keeps ``decay`` of the
    state on average."""
    rs = np.random.RandomState(seed)
    x = rs.randn(b, l, h, p).astype(np.float32)
    dt = (0.1 * np.exp(0.5 * rs.randn(b, l, h))).astype(np.float32)
    A = (np.log(decay) / 0.1 * np.exp(0.3 * rs.randn(h))).astype(np.float32)
    B = (rs.randn(b, l, g, n) / np.sqrt(n)).astype(np.float32)
    C = (rs.randn(b, l, g, n) / np.sqrt(n)).astype(np.float32)
    D = rs.randn(h).astype(np.float32)
    return tuple(jnp.asarray(t) for t in (x, dt, A, B, C, D))


CASES = [  # (batch, length, heads, width, groups, state, chunk, decay)
    (2, 64, 4, 8, 1, 16, 16, 0.95),     # whole chunks, one group
    (1, 50, 4, 8, 1, 16, 16, 0.95),     # the last chunk padded
    (2, 37, 6, 4, 6, 8, 16, 0.99),      # a group a head, weak decay
    (1, 40, 8, 4, 2, 8, 8, 0.5),        # strong decay: the carry dies
    (1, 7, 2, 4, 1, 8, 16, 0.9),        # shorter than one chunk
    (1, 96, 8, 16, 1, 32, 32, 0.999),   # the state carried over chunks
]
IDS = ["whole_chunks", "padded", "group_a_head", "strong_decay",
       "under_a_chunk", "long_memory"]


def _worst(got, want):
    """Largest gap over the largest element of the definition."""
    want = np.asarray(want)
    return float(np.max(np.abs(np.asarray(got) - want)) / np.max(np.abs(
        want)))


@pytest.mark.parametrize("case", CASES, ids=IDS)
def test_the_chunked_scan_is_the_recurrence_forward(case):
    *dims, chunk, decay = case
    args = _inputs(1, *dims, decay)
    got = state_space._ssd_scan(*args, chunk=chunk)
    assert got.shape == args[0].shape and got.dtype == jnp.float32
    assert _worst(got, recurrence(*args)) <= 1e-4


@pytest.mark.parametrize("case", CASES, ids=IDS)
def test_every_gradient_is_the_recurrences(case):
    *dims, chunk, decay = case
    args = _inputs(2, *dims, decay)
    w = jnp.asarray(np.random.RandomState(3).randn(*args[0].shape)
                    .astype(np.float32))

    def loss(fn):
        return lambda *a: jnp.sum(fn(*a) * w)

    got = jax.grad(loss(lambda *a: state_space._ssd_scan(*a, chunk=chunk)),
                   argnums=range(6))(*args)
    want = jax.grad(loss(recurrence), argnums=range(6))(*args)
    for name, a, b in zip("x dt A B C D".split(), got, want):
        assert _worst(a, b) <= 1e-4, name


def test_the_state_crosses_chunks_and_D_adds_its_own():
    """Weak decay over many chunks: what a chunk reads of the earlier ones
    is a large part of its output, and a scan that drops it is far off;
    ``D x`` alone is the output where B or C is zero."""
    *dims, chunk, decay = CASES[-1]
    x, dt, A, B, C, D = _inputs(4, *dims, decay)
    none = jnp.zeros_like(D)
    want = recurrence(x, dt, A, B, C, none)
    first = chunk      # positions past the first chunk
    cut = jnp.concatenate([recurrence(*(t[:, i:i + chunk] for t in
                                         (x, dt)), A,
                                      *(t[:, i:i + chunk] for t in (B, C)),
                                      none)
                           for i in range(0, x.shape[1], chunk)], axis=1)
    assert _worst(cut[:, first:], want[:, first:]) > 0.3
    got = state_space._ssd_scan(x, dt, A, jnp.zeros_like(B), C, D,
                                chunk=chunk)
    np.testing.assert_allclose(got, D[:, None] * x, rtol=1e-6)


def test_bf16_inputs_stay_within_what_bf16_products_give():
    """x, B and C in bfloat16 (the mixer's types; dt and A float32): every
    product rounds its operands to 8 bits of mantissa, ~4e-3 of an
    element, and sums in float32, so the output sits within 2% of the
    float32 definition's largest element and is bf16."""
    *dims, chunk, decay = CASES[1]
    x, dt, A, B, C, D = _inputs(5, *dims, decay)
    bf = [t.astype(jnp.bfloat16) for t in (x, B, C)]
    got = state_space._ssd_scan(bf[0], dt, A, bf[1], bf[2], D, chunk=chunk)
    assert got.dtype == jnp.bfloat16
    want = recurrence(*(t.astype(jnp.float32) for t in bf[:1]), dt, A,
                      *(t.astype(jnp.float32) for t in bf[1:]), D)
    assert 1e-4 < _worst(got.astype(jnp.float32), want) <= 0.02


def test_groups_must_divide_the_heads():
    x, dt, A, B, C, D = _inputs(6, 1, 16, 6, 4, 4, 8, 0.9)    # 4 into 6
    with pytest.raises(ValueError, match="do not divide"):
        state_space._ssd_scan(x, dt, A, B, C, D, chunk=16)


def test_the_op_is_registered_and_runs_under_its_region_scope():
    args = _inputs(7, 1, 32, 4, 8, 1, 16, 0.9)
    text = jax.jit(lambda *a: state_space._ssd_scan(*a, chunk=16)).lower(
        *args).as_text(debug_info=True)
    assert "ssd_scan" in text
    nd = [mx.nd.array(np.asarray(t)) for t in args]
    out = mx.nd.contrib.ssd_scan(*nd, chunk=16)
    np.testing.assert_allclose(out.asnumpy(), recurrence(*args), rtol=1e-4,
                               atol=1e-4)
