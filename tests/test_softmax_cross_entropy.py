"""``softmax_cross_entropy`` as a custom rule (ISSUE 28): value and gradient
against the plain formulation it replaced, which stays here as the
reference; what the rule keeps for the backward; and the op on the tape, in
a TrainStep and under ``create_graph``."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, parallel
from mxnet_tpu.gluon.model_zoo import bert
from mxnet_tpu.ops import registry

OP = registry.get("softmax_cross_entropy").fn


def plain(data, label):
    """The op as it was before the rule: log_softmax, pick, sum."""
    logp = jax.nn.log_softmax(data, axis=-1)
    return -jnp.sum(jnp.take_along_axis(
        logp, label.astype(jnp.int32).reshape(-1, 1), axis=-1))


def case(shape, dtype, label_dtype, seed=0, scale=3.0):
    rng = np.random.RandomState(seed)
    data = jnp.asarray(rng.randn(*shape) * scale, dtype)
    label = jnp.asarray(rng.randint(0, shape[-1], shape[:1]), label_dtype)
    return data, label


# value and gradient of float32 sums over this many classes, relative
_TOL = {"float32": 2e-6, "bfloat16": 1e-2}


@pytest.mark.parametrize("label_dtype", ["int32", "float32"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [(8, 5), (64, 30522), (13, 130)],
                         ids=["8x5", "64x30522", "13x130_rows_not_of_8"])
def test_value_and_gradient_equal_the_plain_formulation(shape, dtype,
                                                        label_dtype):
    data, label = case(shape, dtype, label_dtype)
    value, grad = jax.value_and_grad(OP)(data, label)
    # the reference in float32 from the same rounded input: the op reduces
    # in float32 whatever the input's dtype
    ref_value, ref_grad = jax.value_and_grad(plain)(
        data.astype(jnp.float32), label)
    assert value.dtype == data.dtype and value.shape == ()
    assert grad.dtype == data.dtype and grad.shape == data.shape
    tol = _TOL[dtype]
    np.testing.assert_allclose(np.float32(value), np.float32(ref_value),
                               rtol=tol)
    np.testing.assert_allclose(np.asarray(grad, np.float32),
                               np.asarray(ref_grad), atol=tol)
    # every row of softmax - onehot sums to zero
    assert abs(np.asarray(grad, np.float32).sum(-1)).max() < 50 * tol


def test_the_cotangent_scales_the_gradient():
    data, label = case((16, 9), "float32", "int32")
    _, vjp = jax.vjp(OP, data, label)
    np.testing.assert_allclose(
        np.asarray(vjp(jnp.float32(-2.5))[0]),
        -2.5 * np.asarray(jax.grad(plain)(data, label)), atol=1e-6)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_huge_logits_give_no_inf_or_nan(dtype):
    data = jnp.asarray([[1e4, -1e4, 0.0], [-1e4, -1e4, -1e4],
                        [1e4, 1e4, 1e4]], dtype)
    label = jnp.asarray([1, 2, 0], jnp.int32)
    value, grad = jax.value_and_grad(OP)(data, label)
    assert np.isfinite(np.float32(value))
    assert np.isfinite(np.asarray(grad, np.float32)).all()
    np.testing.assert_allclose(np.asarray(grad, np.float32)[0],
                               [1.0, -1.0, 0.0], atol=1e-6)
    np.testing.assert_allclose(np.float32(value),
                               2e4 + 2 * np.log(3.0), rtol=1e-2)


@pytest.mark.parametrize("label_dtype", ["int32", "float32"])
def test_the_label_gets_no_gradient(label_dtype):
    data, label = case((8, 5), "float32", label_dtype)
    _, vjp = jax.vjp(OP, data, label)
    to_label = vjp(jnp.float32(1.0))[1]
    if label_dtype == "int32":
        assert to_label.dtype == jax.dtypes.float0
    else:
        assert not np.asarray(to_label).any()


# -- what the rule keeps for the backward -------------------------------------

@pytest.mark.parametrize("jitted", [False, True], ids=["eager", "jit"])
def test_the_only_residual_of_datas_shape_is_data_itself(jitted):
    """The leaves of the vjp closure are what the forward keeps alive: of
    ``data``'s size there is one, the argument (no log-softmax, no
    exponentials, no copy), beside the per-row logsumexp and the label."""
    data, label = case((128, 512), "float32", "int32")
    _, vjp = jax.vjp(jax.jit(OP) if jitted else OP, data, label)
    leaves = [x for x in jax.tree_util.tree_leaves(vjp)
              if hasattr(x, "shape")]
    wide = [x for x in leaves if x.size >= data.size]
    assert len(wide) == 1 and wide[0] is data
    assert sorted(x.size for x in leaves) == [128, 128, 128 * 512]


def test_the_plain_formulation_keeps_more():
    """The test above can fail: the old body keeps the float32 log-softmax."""
    data, label = case((128, 512), "float32", "int32")
    _, vjp = jax.vjp(plain, data, label)
    wide = [x for x in jax.tree_util.tree_leaves(vjp)
            if hasattr(x, "shape") and x.size >= data.size]
    assert not (len(wide) == 1 and wide[0] is data)


def test_the_backward_is_one_elementwise_pass_with_no_scatter():
    data, label = case((128, 512), "float32", "int32")
    text = jax.jit(jax.grad(OP)).lower(data, label).as_text()
    assert "scatter" not in text and "gather" not in text
    assert text.count("exponential") == 2    # forward's and backward's


# -- on the tape --------------------------------------------------------------

@pytest.mark.parametrize("label_dtype", ["int32", "float32"])
def test_record_and_backward(label_dtype):
    data, label = case((32, 17), "float32", label_dtype)
    x = mx.nd.array(np.asarray(data))
    x.attach_grad()
    lab = mx.nd.array(np.asarray(label), dtype=label_dtype)
    with autograd.record():
        loss = mx.nd.softmax_cross_entropy(x, lab) / 32
    loss.backward()
    ref_value, ref_grad = jax.value_and_grad(plain)(data, label)
    np.testing.assert_allclose(loss.asnumpy(), np.asarray(ref_value) / 32,
                               rtol=2e-6)
    np.testing.assert_allclose(x.grad.asnumpy(), np.asarray(ref_grad) / 32,
                               atol=1e-7)


def test_create_graph_differentiates_the_rule_again():
    data, label = case((16, 7), "float32", "int32")
    x = mx.nd.array(np.asarray(data))
    x.attach_grad()
    lab = mx.nd.array(np.asarray(label))
    with autograd.record():
        loss = mx.nd.softmax_cross_entropy(x, lab)
        g, = autograd.grad(loss, [x], create_graph=True)
        z = (g * g).sum()
    z.backward()
    second = jax.grad(lambda d: (jax.grad(plain)(d, label) ** 2).sum())(data)
    np.testing.assert_allclose(g.asnumpy(),
                               np.asarray(jax.grad(plain)(data, label)),
                               atol=1e-6)
    np.testing.assert_allclose(x.grad.asnumpy(), np.asarray(second),
                               atol=1e-6)


# -- in a TrainStep -----------------------------------------------------------

VOCAB, SEQ, BATCH = 64, 16, 4


def _rule_loss(logits, labels):
    return mx.nd.softmax_cross_entropy(logits, labels)


def _plain_loss(logits, labels):
    return -mx.nd.pick(mx.nd.log_softmax(logits), labels).sum()


def _one_step(row_loss):
    """tests/test_step_names.py::tiny_step with the loss's body passed in:
    the loss of one step and every parameter after it."""
    mx.random.seed(11)
    np.random.seed(11)
    net = bert.bert_model("bert_3_128_2", vocab_size=VOCAB, max_length=SEQ,
                          dropout=0.0, prefix="bert_")
    net.initialize()

    def loss_fn(out, labels):
        _, _, logits = out
        return row_loss(
            logits.reshape((-1, logits.shape[-1])).astype("float32"),
            labels.reshape((-1,))) / labels.size

    opt = mx.optimizer.Adam(learning_rate=1e-3, multi_precision=True)
    mesh = parallel.make_mesh(shape=(1,), axis_names=("dp",),
                              devices=jax.devices()[:1])
    step = parallel.TrainStep(net, loss_fn, opt, mesh=mesh)
    rng = np.random.RandomState(0)
    tokens = mx.nd.array(rng.randint(0, VOCAB, (BATCH, SEQ)).astype("int32"))
    labels = mx.nd.array(rng.randint(0, VOCAB, (BATCH, SEQ)).astype("int32"))
    before = {k: p.data().asnumpy().copy()
              for k, p in net.collect_params().items()}
    loss = float(step(tokens, labels).asnumpy())
    after = {k: p.data().asnumpy() for k, p in net.collect_params().items()}
    return loss, before, after


def test_trainstep_loss_and_first_update_equal_the_plain_formulations():
    loss, before, after = _one_step(_rule_loss)
    ref_loss, ref_before, ref_after = _one_step(_plain_loss)
    assert abs(loss - ref_loss) <= 2e-6 * abs(ref_loss)
    moved = 0
    for name, start in before.items():
        np.testing.assert_array_equal(start, ref_before[name])
        update, ref_update = after[name] - start, ref_after[name] - start
        # Adam's first step is lr * g / (|g| + eps): compare the leaf as a
        # whole, an element with a gradient near eps may move either way
        gap = np.linalg.norm(update - ref_update)
        assert gap <= 1e-3 * max(np.linalg.norm(ref_update), 1e-12), name
        moved += bool(np.linalg.norm(ref_update))
    assert moved >= len(before) - 2     # the pooler is not reached


# -- each row's value (``per_row``), for a loss that weighs its rows ----------

def plain_rows(data, label):
    logp = jax.nn.log_softmax(data, axis=-1)
    return -jnp.take_along_axis(
        logp, label.astype(jnp.int32).reshape(-1, 1), axis=-1)[:, 0]


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", [(8, 5), (13, 130)])
def test_per_row_values_and_weighted_gradient_equal_the_plain(shape, dtype):
    """Each row's value in float32 whatever the data's type, and a row's
    own cotangent: the gradient of ``sum(w * rows)``."""
    data, label = case(shape, dtype, "int32")
    w = jnp.asarray(np.random.RandomState(3).rand(shape[0]), jnp.float32)
    rows = OP(data, label, per_row=True)
    assert rows.shape == shape[:1] and rows.dtype == jnp.float32
    f32 = data.astype(jnp.float32)
    tol = _TOL[dtype]
    np.testing.assert_allclose(rows, plain_rows(f32, label), rtol=tol,
                               atol=tol)
    grad = jax.grad(lambda x: jnp.sum(w * OP(x, label, per_row=True)))(data)
    want = jax.grad(lambda x: jnp.sum(w * plain_rows(x, label)))(f32)
    assert grad.dtype == data.dtype
    np.testing.assert_allclose(np.asarray(grad, np.float32), want, atol=tol)
    # the rows add up to the summed form
    np.testing.assert_allclose(np.float32(rows.sum()),
                               np.float32(OP(data, label)), rtol=4 * tol)


def test_per_row_keeps_what_the_summed_form_keeps():
    data, label = case((128, 512), "float32", "int32")
    _, vjp = jax.vjp(lambda x, l: OP(x, l, per_row=True), data, label)
    leaves = [x for x in jax.tree_util.tree_leaves(vjp)
              if hasattr(x, "shape")]
    wide = [x for x in leaves if x.size >= data.size]
    assert len(wide) == 1 and wide[0] is data
    assert sorted(x.size for x in leaves) == [128, 128, 128 * 512]


def _summed_as_pr28_left_it(data, label):
    """The op's body before ``per_row`` (PR 28), kept to hold the summed
    form to it instruction for instruction."""
    acc = jnp.promote_types(data.dtype, jnp.float32)

    def at_label(l, shape):
        classes = jax.lax.broadcasted_iota(jnp.int32, shape, len(shape) - 1)
        return classes == l.astype(jnp.int32).reshape(-1, 1)

    @jax.custom_vjp
    def f(x, l):
        return f_fwd(x, l)[0]

    def f_fwd(x, l):
        xf = x.astype(acc)
        lse = jax.nn.logsumexp(xf, axis=-1, keepdims=True)
        picked = jnp.sum(jnp.where(at_label(l, x.shape), xf, 0), axis=-1,
                         keepdims=True)
        return jnp.sum(lse - picked).astype(x.dtype), (x, lse, l)

    def f_bwd(res, g):
        x, lse, l = res
        p = jnp.exp(x.astype(acc) - lse)
        grad = jnp.where(at_label(l, x.shape), p - 1, p) * g.astype(acc)
        return grad.astype(x.dtype), None

    f.defvjp(f_fwd, f_bwd)
    return f(data, label)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_the_summed_form_lowers_as_it_did_before_per_row(dtype):
    """``per_row`` is a branch at trace time: the summed form's program is
    the one PR 28's readings were taken on, to the instruction."""
    data, label = case((16, 32), dtype, "int32")

    def lowered(f):
        def step(x, l):     # one name for both, so that the texts compare
            return jax.value_and_grad(f)(x, l)
        return jax.jit(step).lower(data, label).as_text()

    assert lowered(OP) == lowered(_summed_as_pr28_left_it)
    assert lowered(lambda x, l: OP(x, l, per_row=True).sum()) != lowered(OP)
    # the op under its two attributes on the tape
    x = mx.nd.array(np.asarray(data, np.float32))
    lab = mx.nd.array(np.asarray(label), dtype="int32")
    assert mx.nd.softmax_cross_entropy(x, lab).shape == ()
    assert mx.nd.softmax_cross_entropy(x, lab, per_row=True).shape == (16,)
