"""Engine-contract tests.

Reference models: tests/python/unittest/test_engine.py +
test_exc_handling.py (async error surfacing) and the NaiveEngine
serialized differential oracle (SURVEY §4.2/§5.2 — 'the serialized-vs-
async equivalence trick')."""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, engine, gluon
from mxnet_tpu.base import MXNetError


@pytest.fixture
def naive_engine():
    engine.set_engine_type("NaiveEngine")
    yield
    engine.set_engine_type("ThreadedEnginePerDevice")


def _op_battery(ctx=None):
    """A small cross-section of the op corpus: elemwise, reduce, matmul,
    nn, indexing, RNG-free results returned as numpy."""
    r = np.random.RandomState(42)
    a = mx.nd.array(r.randn(4, 5).astype(np.float32), ctx=ctx)
    b = mx.nd.array(r.randn(5, 3).astype(np.float32), ctx=ctx)
    idx = mx.nd.array(np.array([0, 2], np.int32), ctx=ctx)
    outs = [
        mx.nd.dot(a, b),
        (a * 2 + 1).sum(axis=1),
        mx.nd.softmax(a, axis=-1),
        mx.nd.take(a, idx, axis=0),
        mx.nd.relu(a) - mx.nd.sigmoid(a),
        mx.nd.topk(a, k=2, axis=-1, ret_typ="value"),
    ]
    # a gradient through a couple of ops
    w = mx.nd.array(r.randn(5, 3).astype(np.float32), ctx=ctx)
    w.attach_grad()
    with autograd.record():
        loss = (mx.nd.dot(a, w) ** 2).sum()
    loss.backward()
    outs.append(w.grad)
    return [o.asnumpy() for o in outs]


def test_naive_vs_async_differential():
    """NaiveEngine (serialize after every dispatch) must be numerically
    identical to the default async engine — the reference's determinism
    oracle (MXNET_ENGINE_TYPE=NaiveEngine CI trick)."""
    default = _op_battery()
    engine.set_engine_type("NaiveEngine")
    try:
        assert engine.is_naive()
        naive = _op_battery()
    finally:
        engine.set_engine_type("ThreadedEnginePerDevice")
    assert len(default) == len(naive)
    for d, n in zip(default, naive):
        np.testing.assert_array_equal(d, n)


def test_naive_engine_training_matches(naive_engine):
    mx.random.seed(3)
    net = gluon.nn.Dense(3, in_units=4)
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    x = mx.nd.ones((2, 4))
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward()
    tr.step(2)
    assert np.isfinite(loss.asnumpy()).all()


# ---------------------------------------------------------------------------
# exception handling (test_exc_handling analog)
# ---------------------------------------------------------------------------

def test_invalid_shape_raises_promptly():
    a = mx.nd.ones((2, 3))
    b = mx.nd.ones((4, 5))
    with pytest.raises(Exception):  # noqa: B017 — dot shape mismatch
        mx.nd.dot(a, b)


def test_async_error_surfaces_at_sync_point():
    """The reference test_exc_handling contract: an invalid computation
    queued lazily raises at the NEXT sync point (wait_to_read/asnumpy),
    not at dispatch.  Lazy reshape views reproduce this exactly."""
    out = mx.nd.ones((2,)).reshape((5, 5))  # lazy view — no error yet
    with pytest.raises(Exception, match="reshape"):
        out.asnumpy()  # sync point surfaces the error
    with pytest.raises(Exception, match="reshape"):
        out.wait_to_read()


def test_error_is_synchronous_in_naive_mode(naive_engine):
    # NaiveEngine blocks after every dispatch, so errors become
    # synchronous (reference NaiveEngine semantics); views still
    # validate lazily but any fetch raises immediately after
    out = mx.nd.ones((2,)).reshape((5, 5))
    with pytest.raises(Exception, match="reshape"):
        out.asnumpy()


def test_custom_function_error_propagates():
    class Bad(autograd.Function):
        def forward(self, x):
            raise RuntimeError("boom in custom forward")

        def backward(self, dy):
            return dy

    with pytest.raises(RuntimeError, match="boom"):
        with autograd.record():
            Bad()(mx.nd.ones((2,)))


def test_error_in_hybridized_block_surfaces():
    class Broken(gluon.HybridBlock):
        def hybrid_forward(self, F, x):
            return F.reshape(x, shape=(7, 7))  # impossible for (2, 3)

    net = Broken()
    net.initialize()
    net.hybridize()
    with pytest.raises(Exception):  # noqa: B017 — surfaces at first call
        net(mx.nd.ones((2, 3))).asnumpy()


def test_waitall_noop_and_bulk_scope():
    with engine.bulk(16):
        x = mx.nd.ones((8,)) * 3
    mx.nd.waitall()
    np.testing.assert_array_equal(x.asnumpy(), 3.0)
