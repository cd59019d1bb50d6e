"""Module API tests (reference tests/python/unittest/test_module.py).
Covers fit/score/predict through simple_bind."""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.test_utils import assert_almost_equal


def _mlp_sym():
    data = mx.sym.var("data")
    net = mx.sym.FullyConnected(data, num_hidden=32, name="fc1")
    net = mx.sym.relu(net)
    net = mx.sym.FullyConnected(net, num_hidden=4, name="fc2")
    return mx.sym.SoftmaxOutput(net, name="softmax")


def _toy_data(n=200, d=10, k=4, seed=0):
    rng = np.random.RandomState(seed)
    X = rng.randn(n, d).astype("float32")
    W = rng.randn(d, k).astype("float32")
    y = (X @ W).argmax(axis=1).astype("float32")
    return X, y


def test_module_fit_score_predict():
    X, y = _toy_data()
    it = mx.io.NDArrayIter(X, y, batch_size=20, shuffle=True,
                           label_name="softmax_label")
    mod = mx.mod.Module(_mlp_sym(), data_names=("data",),
                        label_names=("softmax_label",))
    mod.fit(it, num_epoch=6, optimizer="adam",
            optimizer_params=(("learning_rate", 0.01),))
    acc = mod.score(it, "acc")[0][1]
    assert acc > 0.8, f"Module.fit failed to learn (acc={acc})"
    preds = mod.predict(it)
    assert preds[0].shape == (200, 4)


def test_module_forward_backward_update():
    X, y = _toy_data(n=40)
    it = mx.io.NDArrayIter(X, y, batch_size=20, label_name="softmax_label")
    mod = mx.mod.Module(_mlp_sym(), data_names=("data",),
                        label_names=("softmax_label",))
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label)
    mod.init_params(initializer=mx.initializer.Xavier())
    mod.init_optimizer(optimizer="sgd",
                       optimizer_params=(("learning_rate", 0.1),))
    batch = next(iter(it))
    w_before = mod._exec.arg_dict["fc1_weight"].asnumpy().copy()
    mod.forward_backward(batch)
    mod.update()
    w_after = mod._exec.arg_dict["fc1_weight"].asnumpy()
    assert not np.allclose(w_before, w_after)
    assert mod.get_outputs()[0].shape == (20, 4)


def test_module_checkpoint_roundtrip(tmp_path):
    X, y = _toy_data(n=40)
    it = mx.io.NDArrayIter(X, y, batch_size=20, label_name="softmax_label")
    mod = mx.mod.Module(_mlp_sym(), data_names=("data",),
                        label_names=("softmax_label",))
    mod.fit(it, num_epoch=1, optimizer="sgd")
    prefix = str(tmp_path / "mlp")
    mod.save_checkpoint(prefix, 1)

    sym, arg, aux = mx.model.load_checkpoint(prefix, 1)
    assert set(arg) == {"fc1_weight", "fc1_bias", "fc2_weight", "fc2_bias"}
    mod2 = mx.mod.Module(sym, data_names=("data",),
                         label_names=("softmax_label",))
    mod2.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
              for_training=False)
    mod2.init_params(arg_params=arg, aux_params=aux)
    it.reset()
    batch = next(iter(it))
    mod.forward(batch, is_train=False)
    o1 = mod.get_outputs()[0].asnumpy()
    mod2.forward(batch, is_train=False)
    o2 = mod2.get_outputs()[0].asnumpy()
    assert_almost_equal(o1, o2, rtol=1e-5)


def test_module_input_grads():
    X, y = _toy_data(n=20)
    it = mx.io.NDArrayIter(X, y, batch_size=20, label_name="softmax_label")
    mod = mx.mod.Module(_mlp_sym(), data_names=("data",),
                        label_names=("softmax_label",))
    mod.bind(data_shapes=it.provide_data, label_shapes=it.provide_label,
             inputs_need_grad=True)
    mod.init_params()
    batch = next(iter(it))
    mod.forward_backward(batch)
    g = mod.get_input_grads()[0]
    assert g is not None and g.shape == (20, 10)


def test_module_multi_ctx_matches_single(seeded):
    # context=[list] must data-parallelize, and the
    # numerics must match the single-ctx run exactly (grad sum == full-batch
    # grad for a sliced batch with the same params)
    from mxnet_tpu import parallel
    ctxs = parallel.data_parallel_ctxs(2)
    if len(ctxs) < 2:
        pytest.skip("needs 2 devices")
    X, y = _toy_data(n=80)
    def run(ctx):
        mx.random.seed(1234)  # identical init draws across the two runs
        it = mx.io.NDArrayIter(X, y, batch_size=20,
                               label_name="softmax_label")
        mod = mx.mod.Module(_mlp_sym(), data_names=("data",),
                            label_names=("softmax_label",), context=ctx)
        mod.fit(it, num_epoch=3, optimizer="sgd",
                optimizer_params=(("learning_rate", 0.05),
                                  ("rescale_grad", 1.0 / 20)),
                initializer=mx.initializer.Uniform(0.1))
        arg, _ = mod.get_params()
        return {k: v.asnumpy() for k, v in arg.items()}, mod

    single, _ = run(ctxs[0])
    multi, mod = run(ctxs)
    assert len(mod._execs) == 2
    for k in single:
        assert_almost_equal(single[k], multi[k], rtol=1e-4, atol=1e-5)
    # merged outputs span the whole batch
    it = mx.io.NDArrayIter(X, y, batch_size=20, label_name="softmax_label")
    batch = next(iter(it))
    mod.forward(batch, is_train=False)
    outs = mod.get_outputs()
    assert outs[0].shape[0] == 20


def test_module_multi_ctx_requires_divisible_batch():
    from mxnet_tpu import parallel
    ctxs = parallel.data_parallel_ctxs(2)
    if len(ctxs) < 2:
        pytest.skip("needs 2 devices")
    mod = mx.mod.Module(_mlp_sym(), data_names=("data",),
                        label_names=("softmax_label",), context=ctxs)
    with pytest.raises(mx.base.MXNetError, match="divide"):
        mod.bind(data_shapes=[("data", (21, 10))],
                 label_shapes=[("softmax_label", (21,))])


def test_module_multi_ctx_merges_bn_aux(seeded):
    # BN running stats must reflect BOTH batch slices (averaged across
    # executors), not just slice 0's
    from mxnet_tpu import parallel
    ctxs = parallel.data_parallel_ctxs(2)
    if len(ctxs) < 2:
        pytest.skip("needs 2 devices")
    data = mx.sym.var("data")
    net = mx.sym.BatchNorm(data, name="bn", fix_gamma=False)
    net = mx.sym.FullyConnected(net, num_hidden=2, name="fc")
    sym = mx.sym.SoftmaxOutput(net, name="softmax")
    mod = mx.mod.Module(sym, data_names=("data",),
                        label_names=("softmax_label",), context=ctxs)
    mod.bind(data_shapes=[("data", (8, 3))],
             label_shapes=[("softmax_label", (8,))])
    mod.init_params(initializer=mx.initializer.Uniform(0.1))
    mod.init_optimizer(optimizer="sgd")
    # slice 0 gets zeros, slice 1 gets large values: stats must see both
    X = np.concatenate([np.zeros((4, 3), np.float32),
                        np.full((4, 3), 10.0, np.float32)])
    batch = mx.io.DataBatch(data=[mx.nd.array(X)],
                            label=[mx.nd.zeros((8,))])
    mod.forward(batch, is_train=True)
    mod.backward()
    mod.update()
    aux = {n: mod._exec.aux_dict[n].asnumpy() for n in mod._aux_names}
    mean_name = next(n for n in aux if "mean" in n)
    # slice-0-only stats would be ~0; merged stats reflect the 10.0 slice
    assert aux[mean_name].mean() > 0.1, aux[mean_name]
    # every executor carries the SAME merged aux after update
    for e in mod._execs[1:]:
        np.testing.assert_allclose(e.aux_dict[mean_name].asnumpy(),
                                   aux[mean_name], rtol=1e-6)
