"""Symbol API tests (reference tests/python/unittest/test_symbol.py +
test_operator.py symbolic cases).  Covers auto-created
param vars, infer_shape through nn ops, bind/simple_bind fwd+bwd."""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.test_utils import assert_almost_equal


def test_auto_created_param_vars():
    data = mx.sym.var("data")
    fc = mx.sym.FullyConnected(data, num_hidden=10, name="fc1")
    assert fc.list_arguments() == ["data", "fc1_weight", "fc1_bias"]
    fc_nb = mx.sym.FullyConnected(data, num_hidden=10, no_bias=True,
                                  name="fc2")
    assert fc_nb.list_arguments() == ["data", "fc2_weight"]
    conv = mx.sym.Convolution(data, kernel=(3, 3), num_filter=8, name="c1")
    assert conv.list_arguments() == ["data", "c1_weight", "c1_bias"]
    bn = mx.sym.BatchNorm(conv, name="bn1")
    assert bn.list_arguments() == \
        ["data", "c1_weight", "c1_bias", "bn1_gamma", "bn1_beta"]
    assert bn.list_auxiliary_states() == \
        ["bn1_moving_mean", "bn1_moving_var"]


def test_explicit_weight_symbol():
    data = mx.sym.var("data")
    w = mx.sym.var("myw")
    fc = mx.sym.FullyConnected(data, w, num_hidden=10, no_bias=True,
                               name="fc1")
    assert fc.list_arguments() == ["data", "myw"]
    # keyword form too
    fc2 = mx.sym.FullyConnected(data=data, weight=w, num_hidden=10,
                                no_bias=True, name="fc2")
    assert fc2.list_arguments() == ["data", "myw"]


def test_infer_shape_through_nn():
    data = mx.sym.var("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    net = mx.sym.relu(net)
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    arg_shapes, out_shapes, aux_shapes = net.infer_shape(data=(8, 20))
    d = dict(zip(net.list_arguments(), arg_shapes))
    assert d["fc1_weight"] == (32, 20)
    assert d["fc1_bias"] == (32,)
    assert d["fc2_weight"] == (4, 32)
    assert out_shapes == [(8, 4)]

    # through conv + bn
    img = mx.sym.var("img")
    c = mx.sym.Convolution(img, kernel=(3, 3), num_filter=6, pad=(1, 1),
                           name="c1")
    b = mx.sym.BatchNorm(c, name="b1")
    arg_shapes, out_shapes, aux_shapes = b.infer_shape(img=(2, 3, 8, 8))
    d = dict(zip(b.list_arguments(), arg_shapes))
    assert d["c1_weight"] == (6, 3, 3, 3)
    assert d["b1_gamma"] == (6,)
    assert out_shapes == [(2, 6, 8, 8)]
    assert aux_shapes == [(6,), (6,)]


def test_bind_forward_backward():
    data = mx.sym.var("data")
    w = mx.sym.var("w")
    out = mx.sym.broadcast_mul(data, w)
    loss = mx.sym.sum(out)
    x = nd.array(np.array([[1., 2.], [3., 4.]], "float32"))
    wv = nd.array(np.array([[2., 3.], [4., 5.]], "float32"))
    gx = nd.zeros((2, 2))
    gw = nd.zeros((2, 2))
    ex = loss.bind(mx.cpu(), {"data": x, "w": wv},
                   {"data": gx, "w": gw})
    (o,) = ex.forward(is_train=True)
    assert_almost_equal(o.asnumpy(), np.sum([[2, 6], [12, 20]]))
    ex.backward()
    assert_almost_equal(gx.asnumpy(), wv.asnumpy())
    assert_almost_equal(gw.asnumpy(), x.asnumpy())


def test_simple_bind_and_grad():
    data = mx.sym.var("data")
    net = mx.sym.FullyConnected(data, num_hidden=3, name="fc")
    net = mx.sym.sum(net)
    ex = net.simple_bind(ctx=mx.cpu(), data=(4, 5))
    assert ex.arg_dict["fc_weight"].shape == (3, 5)
    x = np.random.randn(4, 5).astype("float32")
    ex.arg_dict["fc_weight"][:] = 0.1
    ex.arg_dict["fc_bias"][:] = 0.0
    ex.forward(is_train=True, data=nd.array(x))
    ex.backward()
    # d sum(xW^T+b) / d b = batch size
    assert_almost_equal(ex.grad_dict["fc_bias"].asnumpy(),
                        np.full(3, 4.0, "float32"))


def test_symbolic_batchnorm_aux_update():
    """BN moving stats must update during symbolic training forward
    (FMutateInputs writeback)."""
    data = mx.sym.var("data")
    bn = mx.sym.BatchNorm(data, name="bn")
    ex = bn.simple_bind(ctx=mx.cpu(), data=(16, 4))
    ex.aux_dict["bn_moving_var"][:] = 1.0
    ex.arg_dict["bn_gamma"][:] = 1.0
    before = ex.aux_dict["bn_moving_mean"].asnumpy().copy()
    x = np.random.randn(16, 4).astype("float32") + 5.0
    ex.forward(is_train=True, data=nd.array(x))
    after = ex.aux_dict["bn_moving_mean"].asnumpy()
    assert not np.allclose(before, after), "moving_mean did not update"


def test_tojson_roundtrip():
    data = mx.sym.var("data")
    net = mx.sym.FullyConnected(data, num_hidden=8, name="fc")
    net = mx.sym.tanh(net)
    js = net.tojson()
    back = mx.sym.load_json(js)
    assert back.list_arguments() == net.list_arguments()
    x = np.random.randn(2, 4).astype("float32")
    wv = np.random.randn(8, 4).astype("float32")
    bv = np.random.randn(8).astype("float32")
    kw = {"data": nd.array(x), "fc_weight": nd.array(wv),
          "fc_bias": nd.array(bv)}
    (o1,) = net.eval(**kw)
    (o2,) = back.eval(**kw)
    assert_almost_equal(o1.asnumpy(), o2.asnumpy())


def test_group_and_internals():
    a = mx.sym.var("a")
    b = mx.sym.var("b")
    s = a + b
    p = a * b
    g = mx.sym.Group(s, p)
    assert g.num_outputs == 2
    outs = g.eval(a=nd.array([2.0]), b=nd.array([3.0]))
    assert_almost_equal(outs[0].asnumpy(), [5.0])
    assert_almost_equal(outs[1].asnumpy(), [6.0])


def test_grouped_output_shapes():
    a = mx.sym.var("a")
    s1 = mx.sym.sum(a)
    s2 = a * 2
    g = mx.sym.Group(s1, s2)
    _, out_shapes, _ = g.infer_shape(a=(3, 2))
    assert out_shapes == [(), (3, 2)]
