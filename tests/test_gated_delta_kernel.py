"""The gated delta rule as a TPU runs it (``ops/linear_attention.py::
_kernel_rule``: the Pallas kernels of kernels/gated_delta.py forward, the
chunked form around ``gdn_solve``'s inverses backward) under the Pallas
interpreter on the CPU: against the token-by-token recurrence and against
the ``jax.numpy`` chunked form it stands in for, out and all five
gradients, over the cases ``test_linear_attention.py`` holds for the op
(decays near 0 and near 1, lengths that are and are not whole chunks,
float32 and bfloat16, a chunk's keys repeated or nearly parallel, decays
that overflow as a ratio, the chunk as an implementation detail); the
solve kernel; the counter; and which lowering a platform and a mesh get.
What the interpreter cannot see (an illegal block, a broadcast Mosaic
lacks) is ``test_chip_compile.py``'s."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu import telemetry
from mxnet_tpu.kernels import gated_delta
from mxnet_tpu.ops import linear_attention, registry
from mxnet_tpu.ops.linear_attention import (_chunked_rule, _gated_delta_rule,
                                            _kernel_rule)

from test_linear_attention import (DECAYS, _inputs, _parallel_keys, _worst,
                                   recurrence)

NAMES = ("dq", "dk", "dv", "dg", "dbeta")


def kernels(q, k, v, g, beta, chunk=64):
    return _kernel_rule(q, k, v, g, beta, chunk, interpret=True)


def _loss(rule):
    def loss(q, k, v, g, beta, weight):
        return (rule(q, k, v, g, beta).astype(jnp.float32) * weight).sum()
    return jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2, 3, 4)))


# one compile a (type, length): the decays are values, not shapes
_KERNELS = jax.jit(kernels)
_KERNELS_GRAD = _loss(kernels)
_CHUNKED_GRAD = _loss(lambda *a: _chunked_rule(*a, 64))
_PLAIN = jax.jit(recurrence)
_PLAIN_GRAD = _loss(recurrence)


@pytest.mark.parametrize("decay", sorted(DECAYS))
@pytest.mark.parametrize("length", [64, 192, 100, 7],
                         ids=["one_chunk", "three_chunks", "ragged_100",
                              "shorter_than_a_chunk"])
@pytest.mark.parametrize("dtype, tol", [("float32", 3e-5),
                                        ("bfloat16", 2e-2)])
def test_kernels_equal_the_recurrence_and_the_chunked_form(
        dtype, tol, length, decay):
    args = _inputs(length, decay, dtype)
    weight = jnp.asarray(np.random.RandomState(1).randn(
        2, length, 3, 8), jnp.float32)
    out = _KERNELS(*args)
    assert out.dtype == args[2].dtype and out.shape == (2, length, 3, 8)
    assert _worst(out, _PLAIN(*args)) <= tol
    assert _worst(out, _chunked_rule(*args, 64)) <= 2 * tol
    got = _KERNELS_GRAD(*args, weight)[1]
    want = _PLAIN_GRAD(*args, weight)[1]
    form = _CHUNKED_GRAD(*args, weight)[1]
    for name, a, b, c in zip(NAMES, got, want, form):
        assert a.dtype == c.dtype and a.shape == c.shape, name
        assert np.isfinite(np.asarray(a, np.float32)).all(), name
        assert _worst(a, b) <= tol, name
        assert _worst(a, c) <= 2 * tol, name


@pytest.mark.parametrize("spread", [0.0, 0.05, 0.3],
                         ids=["repeated", "nearly_parallel", "loose"])
@pytest.mark.parametrize("length", [128, 100], ids=["two_chunks", "ragged"])
@pytest.mark.parametrize("dtype, tol", [("float32", 3e-5),
                                        ("bfloat16", 6e-2)])
def test_kernels_with_parallel_keys_in_a_chunk(dtype, tol, length, spread):
    """Entries under the chunk's diagonal all about 1: a sum of the
    system's powers is 1e19 off here; the substitution is exact algebra
    and holds the op's own tolerances."""
    args = _parallel_keys(length, spread)
    args[:3] = [x.astype(dtype) for x in args[:3]]
    exact = [x.astype(jnp.float32) for x in args[:3]] + args[3:]
    weight = jnp.asarray(np.random.RandomState(1).randn(
        1, length, 2, 8), jnp.float32)
    assert _worst(_KERNELS(*args), _PLAIN(*exact)) <= tol
    got = _KERNELS_GRAD(*args, weight)[1]
    want = _PLAIN_GRAD(*exact, weight)[1]
    for name, a, b in zip(NAMES, got, want):
        assert np.isfinite(np.asarray(a, np.float32)).all(), name
        # as for the op: dg's largest element is small beside its
        # cancelling terms when the keys repeat
        if name == "dg" and spread < 0.3:
            if dtype == "float32":
                assert _worst(a, b) <= 1e-3, name
            continue
        assert _worst(a, b) <= tol, name


@pytest.mark.parametrize("chunk", [8, 16, 64])
def test_the_chunk_is_an_implementation_detail_of_the_kernels(chunk):
    args = _inputs(48, "mixed", "float32", seed=3)
    want = recurrence(*args)
    assert _worst(kernels(*args, chunk=chunk), want) <= 3e-5
    grads = jax.grad(lambda *a: (kernels(*a, chunk=chunk) ** 2).sum(),
                     argnums=(0, 1, 2, 3, 4))(*args)
    wants = jax.grad(lambda *a: (recurrence(*a) ** 2).sum(),
                     argnums=(0, 1, 2, 3, 4))(*args)
    for name, a, b in zip(NAMES, grads, wants):
        assert _worst(a, b) <= 3e-5, name


def test_kernels_with_decays_that_would_overflow_as_a_ratio():
    """exp(G_i) * exp(-G_j) overflows float32 once a chunk's log-decays sum
    past 88; the kernels take the ratio under the mask too."""
    args = _inputs(64, "near_zero", "float32", seed=4)
    args[3] = jnp.full_like(args[3], -4.0)          # 64 x -4 = -256 a chunk
    out = kernels(*args)
    assert np.isfinite(np.asarray(out)).all()
    assert _worst(out, recurrence(*args)) <= 3e-5
    grads = jax.grad(lambda *a: kernels(*a).sum(),
                     argnums=(0, 1, 2, 3, 4))(*args)
    assert all(np.isfinite(np.asarray(g)).all() for g in grads)


def _solved(keys, chunk):
    """``gdn_solve`` alone on ``keys`` (chunks * C, heads, Dk) with no decay
    and writes at full strength: the inverses of ``I + strict_lower(K
    K^T)``, (chunks, heads, C, C)."""
    rows, heads, dk = keys.shape
    k = jnp.asarray(keys, jnp.float32)[None]
    v = jnp.zeros((1, rows, heads, 8), jnp.float32)
    s = gated_delta._shapes(k.shape, 8, chunk)
    out = gated_delta._solve(
        gated_delta._wide(k, k.dtype, s), gated_delta._by_stack(
            jnp.zeros((1, rows, heads)), jnp.ones((1, rows, heads)), s),
        s, jnp.float32, True)
    # (1, head blocks, steps, chunks a step x groups x C, pack x C)
    out = np.asarray(out).reshape(heads // s.hb, -1, s.groups, chunk, s.pack,
                                  chunk)[:, :rows // chunk]
    return np.transpose(out, (1, 0, 2, 4, 3, 5)).reshape(
        rows // chunk, heads, chunk, chunk)


@pytest.mark.parametrize("c, heads", [(8, 4), (16, 2), (64, 2), (64, 1),
                                      (64, 8)])
def test_the_solve_kernel_is_the_inverse(c, heads):
    """Substitution a system a lane, heads stacked and not, one head block
    and two; and every key of a chunk the same, all ones under the
    diagonal, where the inverse is 1 on the diagonal and -1 just under it
    while the system's powers reach 1e18."""
    rs = np.random.RandomState(0)
    lower = np.tril(rs.randn(2, heads, c, c) * 0.3, -1)
    lower[1, 0] = np.tril(np.ones((c, c)), -1)
    # keys whose products are the planted systems: a Cholesky factor of
    # lower + lower^T + a diagonal that makes it positive definite
    gram = lower + np.swapaxes(lower, -1, -2)
    gram += np.eye(c) * (1e-3 + np.abs(gram).sum(-1, keepdims=True))
    keys = np.linalg.cholesky(gram)                      # (2, heads, C, C)
    keys = np.moveaxis(keys, 1, 2).reshape(2 * c, heads, c)
    got = _solved(keys, c)
    want = np.linalg.inv(np.eye(c) + lower)
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-4 * np.abs(want).max())
    np.testing.assert_allclose(got[1, 0], np.eye(c) - np.eye(c, k=-1),
                               atol=2e-4)


def _chunks_counted():
    return {kernel: getattr(telemetry.REGISTRY.get(
        "mxnet_gdn_kernel_chunks_total", labels={"kernel": kernel}),
        "value", 0) for kernel in ("gdn_solve", "gdn_fwd")}


def _grown(before):
    after = _chunks_counted()
    return {k: after[k] - before[k] for k in after}


def test_the_counter_grows_by_batch_heads_and_chunks_a_kernel():
    """Counted where a kernel is built, once a traced call: a forward
    builds ``gdn_solve`` and ``gdn_fwd``; a gradient builds them again and
    the backward's ``gdn_solve``.  (Shapes no other test traces: JAX keeps
    the traces it has.)"""
    args = _inputs(84, "mixed", "float32", seed=6, heads=5)  # 2 x 5 x 2
    before = _chunks_counted()
    jax.make_jaxpr(kernels)(*args)
    assert _grown(before) == {"gdn_solve": 20, "gdn_fwd": 20}
    before = _chunks_counted()
    jax.make_jaxpr(jax.grad(lambda *a: kernels(*a).sum(),
                            argnums=(0, 1, 2, 3, 4)))(*args)
    assert _grown(before) == {"gdn_solve": 40, "gdn_fwd": 20}
    assert "mxnet_gdn_kernel_chunks_total" in telemetry.to_prometheus()


@pytest.mark.parametrize("chunk, heads, dk, dv, dtype, takes", [
    (64, 32, 128, 128, "bfloat16", True), (64, 2, 128, 128, "float32", True),
    (64, 4, 256, 128, "bfloat16", True),
    (64, 3, 128, 128, "bfloat16", False),   # an odd head stacks with none
    (128, 4, 128, 128, "float32", False), (32, 4, 128, 128, "float32", False),
    (8, 4, 128, 128, "float32", False),
    (64, 4, 16, 8, "float32", False), (64, 4, 128, 64, "bfloat16", False),
    (64, 4, 128, 128, "float16", False)])
def test_which_shapes_the_kernels_tiling_takes(chunk, heads, dk, dv, dtype,
                                               takes):
    assert gated_delta.eligible(chunk, heads, dk, dv, dtype) is takes


def _wide_inputs(length, dtype, heads=2):
    args = _inputs(length, "mixed", dtype, seed=7, batch=1, heads=heads,
                   dk=128, dv=128)
    return args


def _weighted(rule):
    return jax.value_and_grad(
        lambda *a: (rule(*a[:5]).astype(jnp.float32) * a[5]).sum(),
        argnums=(0, 1, 2, 3, 4))


@pytest.mark.parametrize("dtype, tol, same", [("float32", 3e-5, 3e-6),
                                              ("bfloat16", 2e-2, 4e-3)])
def test_the_ops_tpu_branch_is_the_kernels_forward_and_the_forms_backward(
        monkeypatch, dtype, tol, same):
    """What a TPU runs, read on the CPU by running the kernels under the
    interpreter: the op's TPU branch against the recurrence, out and all
    five gradients, in both types; its gradients are the chunked form's
    with ``gdn_solve``'s float32 inverses in place of the triangular
    solve's (``same``: a rounding of the result's type apart)."""
    args = _wide_inputs(150, dtype, heads=4)
    exact = [x.astype(jnp.float32) for x in args[:3]] + args[3:]
    weight = jnp.asarray(np.random.RandomState(2).randn(
        1, 150, 4, 128), jnp.float32)
    monkeypatch.setattr(
        linear_attention, "_kernel_rule",
        lambda *a: _kernel_rule(*a, interpret=True))
    monkeypatch.setattr(jax.lax, "platform_dependent",
                        lambda *a, tpu, default: tpu(*a))
    before = _chunks_counted()
    value, grads = _weighted(lambda *a: _gated_delta_rule(*a, chunk=64))(
        *args, weight)
    assert _grown(before) == {"gdn_solve": 2 * 4 * 3, "gdn_fwd": 4 * 3}
    plain_value, plain = _weighted(recurrence)(*exact, weight)
    form_value, form = _weighted(lambda *a: _chunked_rule(*a, 64))(
        *args, weight)
    scale = float(jnp.abs(weight).sum())
    assert abs(float(value) - float(plain_value)) <= tol * scale
    assert abs(float(value) - float(form_value)) <= same * scale
    for name, a, b, c in zip(NAMES, grads, plain, form):
        assert a.dtype == c.dtype and a.shape == c.shape, name
        assert _worst(a, b) <= tol, name
        assert _worst(a, c) <= same, name


def _systems(k, g, beta, length, operand):
    """The chunked form's ``A`` of every chunk, (B, H, N, C, C) float32."""
    pad, n = -length % 64, -(-length // 64)

    def chunks(x):
        x = jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
        return jnp.moveaxis(x.reshape((x.shape[0], n, 64) + x.shape[2:]),
                            3, 1)
    big_g = jnp.cumsum(chunks(g), axis=-1)
    decay = jnp.exp(big_g[..., :, None] - big_g[..., None, :])
    k_beta = chunks(k).astype(jnp.float32) * chunks(beta)[..., None]
    kk = jnp.einsum("...id,...jd->...ij", k_beta.astype(operand),
                    chunks(k).astype(operand), precision="highest",
                    preferred_element_type=jnp.float32)
    return jnp.tril(kk * decay, -1)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_backwards_inverses_are_float32_and_the_triangular_solves(dtype):
    """``inverses`` hands the backward every chunk's inverse in float32
    whatever the operands' type: the gradient through the solve, ``-T^T g
    T^T`` at the highest precision, multiplies with what the triangular
    solve gives and not with its rounding to bfloat16 (4e-3 of an
    entry)."""
    _q, k, _v, g, beta = _wide_inputs(150, dtype, heads=4)
    got = gated_delta.inverses(k, g, beta, 64, True)
    assert got.dtype == jnp.float32 and got.shape == (1, 3, 4, 64, 64)
    want = linear_attention._unit_lower_inverse(
        _systems(k, g, beta, 150, k.dtype))
    np.testing.assert_allclose(jnp.moveaxis(got, 2, 1), want, atol=2e-5)


def test_float32_keys_beside_bfloat16_values_take_the_values_type():
    """q and k in float32 beside v in bfloat16: every product takes its
    operands in v's type, so the backward's systems are made from k as the
    forward and the chunked form round it, not from k as given."""
    args = _wide_inputs(150, "float32")
    args[2] = args[2].astype(jnp.bfloat16)
    weight = jnp.asarray(np.random.RandomState(2).randn(
        1, 150, 2, 128), jnp.float32)
    rounded = [x.astype(jnp.bfloat16) for x in args[:2]] + args[2:]
    np.testing.assert_array_equal(
        np.asarray(kernels(*args), np.float32),
        np.asarray(kernels(*rounded), np.float32))
    got = _weighted(kernels)(*args, weight)[1]
    form = _weighted(lambda *a: _chunked_rule(*a, 64))(*args, weight)[1]
    for name, a, b in zip(NAMES, got, form):
        assert a.dtype == b.dtype, name
        assert _worst(a, b) <= (3e-6 if a.dtype == jnp.float32 else 4e-3), \
            name


@pytest.mark.parametrize("heads, builds", [(8, True), (6, False)])
def test_under_a_mesh_the_tiling_is_asked_about_a_devices_heads(heads,
                                                                builds):
    """A ``TrainStep`` traced over dp2 x tp2 gives a device half the heads:
    4 of 8 stack in pairs and get the kernels (built when the op is
    traced, whatever the platform); 3 of 6 do not, and the op is the
    chunked form there though 6 heads on one device would be the
    kernels'."""
    from mxnet_tpu import parallel
    args = _wide_inputs(128, "bfloat16", heads=heads)
    args = [jnp.concatenate([x, x]) for x in args]      # two rows over dp
    assert gated_delta.eligible(64, heads, 128, 128, args[2].dtype)
    mesh = parallel.make_mesh(shape=(2, 2), axis_names=("dp", "tp"),
                              devices=jax.devices()[:4])

    def op(*a):
        with registry.step_layout_scope(mesh, ("dp",)):
            return _gated_delta_rule(*a, chunk=64)

    before = _chunks_counted()
    text = jax.jit(op).lower(*args).as_text()
    assert "stablehlo.while" in text        # the CPU's lowering either way
    block = 1 * (heads // 2) * 2 if builds else 0   # a device's: x chunks
    assert _grown(before) == {"gdn_solve": block, "gdn_fwd": block}


@pytest.mark.parametrize("dtype, tol", [("float32", 3e-5),
                                        ("bfloat16", 2e-2)])
def test_kernels_at_the_cells_head_widths(dtype, tol):
    """128-wide keys and values, 4 heads (one grid step's block), two
    chunks and a ragged tail: the shapes' rule picks what the cell runs."""
    args = _wide_inputs(150, dtype, heads=4)
    exact = [x.astype(jnp.float32) for x in args[:3]] + args[3:]
    weight = jnp.asarray(np.random.RandomState(2).randn(
        1, 150, 4, 128), jnp.float32)
    assert _worst(_KERNELS(*args), _PLAIN(*exact)) <= tol
    got = _KERNELS_GRAD(*args, weight)[1]
    want = _PLAIN_GRAD(*exact, weight)[1]
    for name, a, b in zip(NAMES, got, want):
        assert _worst(a, b) <= tol, name
