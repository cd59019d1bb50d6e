"""Estimator, CustomOp, optimize_for, opperf, im2rec, parse_log tests
(remaining component gaps)."""

import io
import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon
from mxnet_tpu.base import MXNetError

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# estimator
# ---------------------------------------------------------------------------

def _toy_loader(n=64, d=8, k=4, batch=16, seed=0):
    r = np.random.RandomState(seed)
    X = mx.nd.array(r.randn(n, d).astype(np.float32))
    y = mx.nd.array(r.randint(0, k, (n,)))
    return gluon.data.DataLoader(gluon.data.ArrayDataset(X, y),
                                 batch_size=batch)


def test_estimator_fit_and_evaluate(seeded):
    from mxnet_tpu.gluon.contrib.estimator import Estimator
    net = gluon.nn.Dense(4, in_units=8)
    net.initialize(mx.initializer.Xavier())
    est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                    train_metrics=["acc"])
    loader = _toy_loader()
    est.fit(loader, epochs=3)
    rows = est.evaluate(loader)
    names = [r[0] for r in rows]
    assert any("loss" in n for n in names)
    assert any("accuracy" in n for n in names)


def test_estimator_early_stopping(seeded):
    from mxnet_tpu.gluon.contrib.estimator import (Estimator,
                                                   EarlyStoppingHandler)
    net = gluon.nn.Dense(4, in_units=8)
    net.initialize()
    est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss(),
                    train_metrics=["acc"],
                    trainer=gluon.Trainer(net.collect_params(), "sgd",
                                          {"learning_rate": 0.0}))
    # lr=0: metric never improves → stop after patience epochs, not 50
    stopper = EarlyStoppingHandler(monitor=est.train_loss_metric,
                                   patience=2, min_delta=1e-9, mode="min")
    est.fit(_toy_loader(), epochs=50, event_handlers=[stopper])
    assert stopper.stopped_epoch is not None
    assert stopper.stopped_epoch <= 5


def test_estimator_checkpoint_handler(seeded, tmp_path):
    from mxnet_tpu.gluon.contrib.estimator import (CheckpointHandler,
                                                   Estimator)
    net = gluon.nn.Dense(2, in_units=8)
    net.initialize()
    est = Estimator(net, gluon.loss.SoftmaxCrossEntropyLoss())
    ck = CheckpointHandler(str(tmp_path), model_prefix="m")
    est.fit(_toy_loader(k=2), epochs=2, event_handlers=[ck])
    assert (tmp_path / "m-epoch0.params").exists()
    assert (tmp_path / "m-epoch1.params").exists()


# ---------------------------------------------------------------------------
# CustomOp
# ---------------------------------------------------------------------------

@mx.operator.register("test_straight_through")
class _STProp(mx.operator.CustomOpProp):
    """Sign forward, identity backward — autodiff would give zero grad,
    so this proves op.backward (not autodiff) drives the vjp."""

    def create_operator(self, ctx, shapes, dtypes):  # noqa: ARG002
        class Op(mx.operator.CustomOp):
            def forward(self, is_train, req, in_data, out_data, aux):  # noqa: ARG002
                self.assign(out_data[0], req[0], mx.nd.sign(in_data[0]))

            def backward(self, req, out_grad, in_data, out_data, in_grad,
                         aux):  # noqa: ARG002
                self.assign(in_grad[0], req[0], out_grad[0])

        return Op()


def test_custom_op_straight_through(seeded):
    x = mx.nd.array(np.array([0.7, -0.2, 1.5], np.float32))
    x.attach_grad()
    with autograd.record():
        y = mx.nd.Custom(x, op_type="test_straight_through")
    y.backward(mx.nd.array(np.array([1.0, 2.0, 3.0], np.float32)))
    np.testing.assert_array_equal(y.asnumpy(), [1.0, -1.0, 1.0])
    # identity backward, NOT sign's zero autodiff grad
    np.testing.assert_array_equal(x.grad.asnumpy(), [1.0, 2.0, 3.0])


def test_custom_op_kwargs_are_strings():
    seen = {}

    @mx.operator.register("test_kwarg_echo")
    class P(mx.operator.CustomOpProp):
        def __init__(self, alpha="1"):
            super().__init__()
            seen["alpha"] = alpha

        def create_operator(self, ctx, shapes, dtypes):  # noqa: ARG002
            class Op(mx.operator.CustomOp):
                def forward(self, is_train, req, in_data, out_data, aux):  # noqa: ARG002
                    self.assign(out_data[0], req[0], in_data[0])

            return Op()

    mx.nd.Custom(mx.nd.ones((2,)), op_type="test_kwarg_echo", alpha=2.5)
    assert seen["alpha"] == "2.5"  # reference attr-dict string round-trip


def test_custom_op_errors():
    with pytest.raises(MXNetError, match="not registered"):
        mx.nd.Custom(mx.nd.ones((2,)), op_type="nope_never")
    with pytest.raises(MXNetError, match="expects 1 inputs"):
        mx.nd.Custom(mx.nd.ones((2,)), mx.nd.ones((2,)),
                     op_type="test_straight_through")


# ---------------------------------------------------------------------------
# optimize_for
# ---------------------------------------------------------------------------

def test_optimize_for_builtin_and_custom():
    s = mx.sym.var("x") * 2
    assert s.optimize_for("TPU") is s
    assert s.optimize_for("default") is s
    with pytest.raises(MXNetError, match="not registered"):
        s.optimize_for("tensorrt")

    calls = {}

    @mx.symbol.register_backend("test_backend")
    def _pass(sym, args, aux, **kwargs):
        calls["kwargs"] = kwargs
        return sym

    assert s.optimize_for("test_backend", flag=3) is s
    assert calls["kwargs"] == {"flag": 3}


# ---------------------------------------------------------------------------
# tools
# ---------------------------------------------------------------------------

def test_opperf_rows():
    sys.path.insert(0, os.path.join(REPO, "benchmark", "opperf"))
    try:
        import opperf
    finally:
        sys.path.pop(0)
    rows = opperf.run(["dot", "softmax", "relu"], output="json", runs=2)
    by_op = {r["op"]: r for r in rows}
    assert by_op["dot"]["fwd_ms"] > 0
    assert "fwd_bwd_ms" in by_op["dot"]


def test_im2rec_roundtrip(tmp_path):
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import im2rec
    finally:
        sys.path.pop(0)
    # build a tiny image tree with cv2 (baked in)
    import cv2
    root = tmp_path / "imgs"
    for cls in ("cat", "dog"):
        (root / cls).mkdir(parents=True)
        for i in range(3):
            img = np.random.RandomState(i).randint(
                0, 255, (8, 8, 3), np.uint8)
            cv2.imwrite(str(root / cls / f"{i}.jpg"), img)
    prefix = str(tmp_path / "data")
    lst, n, classes = im2rec.make_list(prefix, str(root))
    assert n == 6 and classes == ["cat", "dog"]
    n, skipped = im2rec.make_rec(prefix, str(root))
    assert n == 6 and skipped == 0
    # read back through the framework's RecordIO
    from mxnet_tpu import recordio
    rec = recordio.MXIndexedRecordIO(prefix + ".idx", prefix + ".rec", "r")
    header, payload = recordio.unpack(rec.read_idx(0))
    assert header.label in (0.0, 1.0)
    img = cv2.imdecode(np.frombuffer(payload, np.uint8), cv2.IMREAD_COLOR)
    assert img.shape == (8, 8, 3)


def test_parse_log():
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import parse_log
    finally:
        sys.path.pop(0)
    lines = [
        "INFO Epoch[0] Train-accuracy=0.50",
        "INFO Epoch[0] Validation-accuracy=0.40",
        "INFO Epoch[1] Train-accuracy=0.80",
        "INFO Epoch[1] Batch [20] Speed: 150.0 samples/sec",
    ]
    table = parse_log.parse(lines)
    assert table[0]["train-accuracy"] == 0.5
    assert table[0]["validation-accuracy"] == 0.4
    assert table[1]["samples"] == 150.0
    out = io.StringIO()
    parse_log.render(table, "md", out)
    assert "| epoch |" in out.getvalue()


def test_library_load_python_oplib(tmp_path):
    """mx.library.load (reference python/mxnet/library.py MXLoadLib role):
    a python op library registers through the public seams and its ops
    land on mx.nd; .so files get the documented guidance error."""
    import mxnet_tpu as mx
    lib = os.path.join(str(tmp_path), "myops.py")
    with open(lib, "w") as f:
        f.write(
            "from mxnet_tpu.ops.registry import register\n"
            "@register('my_plus_two')\n"
            "def _my_plus_two(x):\n"
            "    return x + 2\n")
    new = mx.library.load(lib, verbose=False)
    assert "my_plus_two" in new
    out = mx.nd.my_plus_two(mx.nd.ones((2, 2)))
    np.testing.assert_allclose(out.asnumpy(), np.full((2, 2), 3.0))
    assert lib in mx.library.loaded_libraries()
    # symbol namespace too
    s = mx.sym.my_plus_two(mx.sym.var("x"))
    ex = s.bind(mx.cpu(), {"x": mx.nd.zeros((2,))})
    np.testing.assert_allclose(ex.forward()[0].asnumpy(), [2.0, 2.0])
    with pytest.raises(mx.MXNetError, match="PYTHON"):
        fake = os.path.join(str(tmp_path), "lib.so")
        open(fake, "wb").close()
        mx.library.load(fake)
    with pytest.raises(mx.MXNetError, match="registered no"):
        empty = os.path.join(str(tmp_path), "empty.py")
        with open(empty, "w") as f:
            f.write("x = 1\n")
        mx.library.load(empty)


def test_library_load_idempotent_and_rolls_back(tmp_path):
    """Re-loading a library returns the cached ops; a library that raises
    mid-registration rolls back so a fixed version can load (review
    regressions)."""
    import mxnet_tpu as mx
    lib = os.path.join(str(tmp_path), "relib.py")
    with open(lib, "w") as f:
        f.write("from mxnet_tpu.ops.registry import register\n"
                "@register('relib_op')\n"
                "def _f(x):\n    return x * 3\n")
    first = mx.library.load(lib, verbose=False)
    assert mx.library.load(lib, verbose=False) == first   # no re-exec crash
    broken = os.path.join(str(tmp_path), "broken.py")
    with open(broken, "w") as f:
        f.write("from mxnet_tpu.ops.registry import register\n"
                "@register('broken_ok')\n"
                "def _a(x):\n    return x\n"
                "raise RuntimeError('boom')\n")
    with pytest.raises(RuntimeError, match="boom"):
        mx.library.load(broken, verbose=False)
    with open(broken, "w") as f:   # fixed version must now load cleanly
        f.write("from mxnet_tpu.ops.registry import register\n"
                "@register('broken_ok')\n"
                "def _a(x):\n    return x + 1\n")
    assert "broken_ok" in mx.library.load(broken, verbose=False)
