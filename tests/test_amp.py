"""AMP + monitor + contrib namespace tests.

Reference models: tests/python/unittest/test_amp.py (lists consistency,
convert_model dtype checks) and the monitor example in
python/mxnet/monitor.py docstrings.
"""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import amp, autograd, gluon


@pytest.fixture
def amp_off_after():
    yield
    amp.off()


def test_lazy_names_resolve():
    # every advertised lazy must import
    for name in ("amp", "monitor", "contrib", "gluon", "optimizer", "metric",
                 "initializer", "lr_scheduler", "io", "image", "kvstore",
                 "profiler", "runtime", "symbol", "parallel", "test_utils",
                 "recordio", "callback", "model", "util", "numpy",
                 "numpy_extension", "module"):
        assert getattr(mx, name) is not None
    assert hasattr(mx, "amp")
    assert not hasattr(mx, "definitely_not_a_module")


def test_amp_op_lists_disjoint():
    lp = set(amp.list_lp16_ops())
    f32 = set(amp.list_fp32_ops())
    widest = set(amp.list_widest_ops())
    assert not lp & f32
    assert not lp & widest
    assert not f32 & widest
    from mxnet_tpu.ops import registry
    known = set(registry.list_ops())
    for name in lp | f32 | widest:
        assert name in known, f"amp list references unknown op {name}"


def test_amp_init_casts_matmul(amp_off_after):
    amp.init()
    a = mx.nd.ones((4, 4))
    out = mx.nd.dot(a, a)
    assert str(out.dtype) == "bfloat16"
    # fp32-forced op keeps float32 even from bf16 inputs
    s = mx.nd.softmax(out)
    assert str(s.dtype) == "float32"
    amp.off()
    assert str(mx.nd.dot(a, a).dtype) == "float32"


def test_amp_widest_cast(amp_off_after):
    amp.init()
    import ml_dtypes
    a = mx.nd.ones((4,)).astype(ml_dtypes.bfloat16)
    b = mx.nd.ones((4,))  # float32
    out = mx.nd.broadcast_add(a, b)
    assert str(out.dtype) == "float32"


def test_amp_hybridized_retraces(amp_off_after):
    net = gluon.nn.Dense(8)
    net.initialize()
    net.hybridize()
    x = mx.nd.ones((2, 4))
    assert str(net(x).dtype) == "float32"
    amp.init()
    assert str(net(x).dtype) == "bfloat16"
    amp.off()
    assert str(net(x).dtype) == "float32"


def test_amp_training_step_matches_fp32_shape(amp_off_after):
    amp.init()
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    net.initialize(mx.initializer.Xavier())
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    amp.init_trainer(tr)
    assert tr._amp_loss_scaler.loss_scale == 1.0  # bf16: no scaling
    lossf = gluon.loss.SoftmaxCrossEntropyLoss()
    x = mx.nd.array(np.random.RandomState(0).randn(8, 8))
    y = mx.nd.array(np.random.RandomState(1).randint(0, 4, (8,)))
    with autograd.record():
        loss = lossf(net(x), y)
    before = [p.data().asnumpy().copy() for p in net.collect_params().values()]
    with amp.scale_loss(loss, tr) as scaled:
        scaled.backward()
    tr.step(8)
    after = [p.data().asnumpy() for p in net.collect_params().values()]
    assert any(not np.allclose(b, a) for b, a in zip(before, after))
    assert np.isfinite(loss.asnumpy()).all()


def test_loss_scaler_dynamic_fp16():
    sc = amp.LossScaler(init_scale=256.0, scale_window=2,
                        target_dtype="float16")
    good = mx.nd.ones((3,))
    bad = mx.nd.array(np.array([1.0, np.inf, 0.0]))
    assert sc.has_overflow([bad])
    assert sc.loss_scale == 128.0
    assert not sc.has_overflow([good])
    assert not sc.has_overflow([good])
    assert sc.loss_scale == 256.0  # doubled after scale_window clean steps


def test_overflow_skips_update(amp_off_after):
    amp.init(target_dtype="float16")
    net = gluon.nn.Dense(2)
    net.initialize()
    x = mx.nd.ones((2, 3))
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.5})
    amp.init_trainer(tr)
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    # poison the gradient
    w = list(net.collect_params().values())[0]
    g = w.list_grad()[0]
    g[:] = mx.nd.array(np.full(g.shape, np.inf, np.float32))
    before = w.data().asnumpy().copy()
    scale0 = tr._amp_loss_scaler.loss_scale
    tr.step(1)
    assert np.allclose(w.data().asnumpy(), before)  # update skipped
    assert tr._amp_loss_scaler.loss_scale == scale0 / 2


def test_amp_grads_stay_param_dtype(amp_off_after):
    # cast sits inside the differentiated fn, so f32 params get f32 grads
    amp.init()
    net = gluon.nn.Dense(4)
    net.initialize()
    x = mx.nd.ones((2, 3))
    with autograd.record():
        out = net(x)
    out.backward()
    for p in net.collect_params().values():
        assert str(np.dtype(p.list_grad()[0].dtype)) == "float32"


def test_unscale_then_step_no_double_divide(amp_off_after):
    amp.init(target_dtype="float16")
    net = gluon.nn.Dense(1, use_bias=False)
    net.initialize(mx.initializer.One())
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 1.0})
    amp.init_trainer(tr)
    tr._amp_loss_scaler.loss_scale = 256.0  # fp16-representable for the test
    x = mx.nd.ones((1, 1))
    with autograd.record():
        loss = net(x).sum()
        with amp.scale_loss(loss, tr) as scaled:
            scaled.backward()
    w = list(net.collect_params().values())[0]
    amp.unscale(tr)  # grads now unscaled in place
    g = w.list_grad()[0].asnumpy()
    assert np.allclose(g, 1.0), g  # dL/dw = x = 1 after unscale
    tr.step(1)
    # w <- 1 - lr*1 = 0; double-divide would give w ≈ 1 - 1/65536
    assert np.allclose(w.data().asnumpy(), 0.0, atol=1e-3)


def test_overflow_skip_update_on_kvstore(amp_off_after):
    amp.init(target_dtype="float16")
    net = gluon.nn.Dense(2)
    net.initialize()
    x = mx.nd.ones((2, 3))
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.5},
                       kvstore="local", update_on_kvstore=True)
    amp.init_trainer(tr)
    with autograd.record():
        loss = net(x).sum()
    loss.backward()
    w = list(net.collect_params().values())[0]
    g = w.list_grad()[0]
    g[:] = mx.nd.array(np.full(g.shape, np.nan, np.float32))
    before = w.data().asnumpy().copy()
    tr.step(1)
    assert np.isfinite(w.data().asnumpy()).all()
    assert np.allclose(w.data().asnumpy(), before)


def test_monitor_safe_under_hybridize_trace():
    mon = mx.monitor.Monitor(interval=1)
    mon.install()
    try:
        net = gluon.nn.Dense(3)
        net.initialize()
        net.hybridize()
        mon.tic()
        net(mx.nd.ones((2, 2)))
        rows = mon.toc()  # must not raise on trace-time tracers
        assert all(isinstance(r[2], float) for r in rows)
    finally:
        mon.uninstall()


def test_convert_hybrid_block(amp_off_after):
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(8), gluon.nn.BatchNorm(), gluon.nn.Dense(2))
    net.initialize()
    net(mx.nd.ones((2, 4)))
    amp.convert_hybrid_block(net, "bfloat16")
    dts = {name: str(np.dtype(p.dtype)) for name, p in net.collect_params().items()}
    for name, dt in dts.items():
        if any(m in name for m in ("gamma", "beta", "running_", "moving_")):
            assert dt == "float32", (name, dt)
        else:
            assert dt == "bfloat16", (name, dt)


def test_convert_model_symbolic(amp_off_after):
    data = mx.sym.Variable("data")
    net = mx.sym.FullyConnected(data, num_hidden=4, name="fc")
    arg = {"fc_weight": mx.nd.ones((4, 3)), "fc_bias": mx.nd.zeros((4,))}
    sym2, arg2, aux2 = amp.convert_model(net, arg, {}, "bfloat16")
    assert sym2 is net
    assert str(arg2["fc_weight"].dtype) == "bfloat16"
    assert aux2 == {}


def test_monitor_collects_stats():
    mon = mx.monitor.Monitor(interval=1, pattern=".*")
    mon.install()
    try:
        mon.tic()
        a = mx.nd.ones((3, 3))
        (a * 2).sum()
        rows = mon.toc()
        assert rows, "monitor captured nothing"
        names = [r[1] for r in rows]
        assert any("mul" in n or "sum" in n for n in names)
        assert all(isinstance(r[2], float) for r in rows)
    finally:
        mon.uninstall()


def test_monitor_interval_and_pattern():
    mon = mx.monitor.Monitor(interval=2, pattern=".*sum.*")
    mon.install()
    try:
        mon.tic()  # step 0: active
        mx.nd.ones((2,)).sum()
        rows0 = mon.toc()
        assert rows0 and all("sum" in r[1] for r in rows0)
        mon.tic()  # step 1: inactive
        mx.nd.ones((2,)).sum()
        assert mon.toc() == []
    finally:
        mon.uninstall()


def test_contrib_namespace():
    assert mx.contrib.amp is mx.amp
    out = mx.contrib.ndarray.div_sqrt_dim(mx.nd.ones((2, 16)))
    assert np.allclose(out.asnumpy(), 1.0 / 4.0)
    with pytest.raises(AttributeError, match="StableHLO"):
        mx.contrib.onnx  # noqa: B018
    # INT8 quantization is rebuilt (N11/P19): the namespace must resolve
    assert hasattr(mx.contrib.quantization, "quantize_net")
