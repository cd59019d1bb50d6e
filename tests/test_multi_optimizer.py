"""Multi-tensor fused optimizer tests (reference
src/operator/optimizer_op.cc multi_sgd_update / multi_mp_sgd_* kernels +
the optimizer aggregation the reference drives through
MXNET_OPTIMIZER_AGGREGATION_SIZE)."""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, profiler


def _rand(shape, seed):
    return np.random.RandomState(seed).randn(*shape).astype(np.float32)


def test_multi_sgd_update_matches_singles():
    ws = [_rand((4, 3), i) for i in range(3)]
    gs = [_rand((4, 3), 10 + i) for i in range(3)]
    lrs = np.array([0.1, 0.05, 0.2], np.float32)
    wds = np.array([0.0, 0.01, 0.001], np.float32)
    outs = nd.multi_sgd_update(
        *[x for w, g in zip(ws, gs) for x in (nd.array(w), nd.array(g))],
        nd.array(lrs), nd.array(wds), rescale_grad=0.5, num_weights=3)
    for i in range(3):
        single = nd.sgd_update(nd.array(ws[i]), nd.array(gs[i]),
                               lr=float(lrs[i]), wd=float(wds[i]),
                               rescale_grad=0.5)
        np.testing.assert_allclose(outs[i].asnumpy(), single.asnumpy(),
                                   rtol=1e-6)


def test_multi_sgd_mom_update_matches_singles():
    ws = [_rand((5,), i) for i in range(2)]
    gs = [_rand((5,), 7 + i) for i in range(2)]
    ms = [_rand((5,), 20 + i) for i in range(2)]
    lrs = np.array([0.1, 0.3], np.float32)
    wds = np.array([0.01, 0.0], np.float32)
    ins = [x for w, g, m in zip(ws, gs, ms)
           for x in (nd.array(w), nd.array(g), nd.array(m))]
    outs = nd.multi_sgd_mom_update(*ins, nd.array(lrs), nd.array(wds),
                                   momentum=0.9, num_weights=2)
    for i in range(2):
        sw, sm = nd.sgd_mom_update(
            nd.array(ws[i]), nd.array(gs[i]), nd.array(ms[i]),
            lr=float(lrs[i]), wd=float(wds[i]), momentum=0.9)
        np.testing.assert_allclose(outs[2 * i].asnumpy(), sw.asnumpy(),
                                   rtol=1e-6)
        np.testing.assert_allclose(outs[2 * i + 1].asnumpy(), sm.asnumpy(),
                                   rtol=1e-6)


def test_multi_mp_sgd_update_casts_and_masters():
    import ml_dtypes
    w16 = nd.array(_rand((6,), 0).astype(ml_dtypes.bfloat16))
    g16 = nd.array(_rand((6,), 1).astype(ml_dtypes.bfloat16))
    w32 = w16.astype(np.float32)
    outs = nd.multi_mp_sgd_update(w16, g16, w32,
                                  nd.array(np.array([0.1], np.float32)),
                                  nd.array(np.array([0.0], np.float32)),
                                  num_weights=1)
    want32 = w32.asnumpy() - 0.1 * g16.astype(np.float32).asnumpy()
    np.testing.assert_allclose(outs[1].asnumpy(), want32, rtol=1e-6)
    assert outs[0].dtype == w16.dtype
    np.testing.assert_allclose(outs[0].astype(np.float32).asnumpy(),
                               want32.astype(ml_dtypes.bfloat16)
                               .astype(np.float32), rtol=1e-6)


def _train(agg, steps=3, n_layers=6, seed=5):
    mx.random.seed(seed)
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        for _ in range(n_layers):
            net.add(gluon.nn.Dense(8, activation="relu", in_units=8))
    net.initialize(mx.initializer.Xavier())
    tr = gluon.Trainer(net.collect_params(), "sgd",
                       {"learning_rate": 0.05, "momentum": 0.9,
                        "wd": 0.01, "aggregate_num": agg})
    lf = gluon.loss.L2Loss()
    r = np.random.RandomState(3)
    x = mx.nd.array(r.randn(4, 8).astype(np.float32))
    y = mx.nd.array(r.randn(4, 8).astype(np.float32))
    for _ in range(steps):
        with autograd.record():
            loss = lf(net(x), y)
        loss.backward()
        tr.step(4)
    # key by the name suffix: the gluon global name counters advance
    # between runs (hybridsequentialN_ prefixes differ)
    return {k.split("_", 1)[-1]: v.data().asnumpy()
            for k, v in net.collect_params().items()}


def test_trainer_aggregated_matches_per_param(monkeypatch):
    """aggregate_num>1 routes through multi_sgd_mom_update groups; params
    after 3 steps match the per-param path bit-for-bit in formula.
    (Flat-buffer fusion off: it supersedes aggregation when enabled.)"""
    monkeypatch.setenv("MXNET_OPTIMIZER_FUSED", "0")
    base = _train(agg=0)
    fused = _train(agg=4)
    assert base.keys() == fused.keys()
    for k in base:
        np.testing.assert_allclose(fused[k], base[k], rtol=1e-6, atol=1e-7,
                                   err_msg=k)


@pytest.mark.slow  # >10s on the tier-1 budget clock (r7 audit); runs in the CI slow lane
def test_aggregation_reduces_dispatch_count(monkeypatch):
    """The point of the multi-tensor path: fewer host dispatches per step
    (reference: one multi_sgd kernel per aggregate group).  Counted via
    the profiler's dispatch ledger.  Runs with the flat-buffer fused
    optimizer OFF — it supersedes aggregation when enabled (ISSUE 5;
    tests/test_optimizer_fusion.py covers that path)."""
    monkeypatch.setenv("MXNET_OPTIMIZER_FUSED", "0")

    def count_update_dispatches(agg):
        mx.random.seed(1)
        net = gluon.nn.HybridSequential()
        with net.name_scope():
            for _ in range(8):
                net.add(gluon.nn.Dense(4, in_units=4))
        net.initialize()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1,
                            "aggregate_num": agg})
        lf = gluon.loss.L2Loss()
        x = mx.nd.array(np.ones((2, 4), np.float32))
        y = mx.nd.array(np.zeros((2, 4), np.float32))
        with autograd.record():
            loss = lf(net(x), y)
        loss.backward()
        profiler.set_state("run")
        tr.step(2)
        table = profiler.dumps(reset=True)
        profiler.set_state("stop")

        def calls(op):
            for line in table.splitlines():
                parts = line.split()
                if parts and parts[0] == op:
                    return int(parts[1])
            return 0

        return calls("sgd_update"), calls("multi_sgd_update")

    single_n, single_m = count_update_dispatches(agg=0)
    agg_n, agg_m = count_update_dispatches(agg=4)
    assert single_n == 16 and single_m == 0   # 8 weights + 8 biases
    assert agg_n == 0 and agg_m >= 1          # grouped dispatches only
    assert agg_m <= 4                          # ceil(16/4)


def test_multi_sgd_preserves_half_dtype():
    """f32 lr/wd vectors must not promote bf16 params (review regression:
    the fused path silently flipped weights to f32 after one step)."""
    import ml_dtypes
    w = nd.array(_rand((4,), 0).astype(ml_dtypes.bfloat16))
    g = nd.array(_rand((4,), 1).astype(ml_dtypes.bfloat16))
    m = nd.array(np.zeros(4, ml_dtypes.bfloat16))
    outs = nd.multi_sgd_update(w, g,
                               nd.array(np.array([0.1], np.float32)),
                               nd.array(np.array([0.0], np.float32)),
                               num_weights=1)
    assert outs.dtype == w.dtype if not isinstance(outs, list) \
        else outs[0].dtype == w.dtype
    outs2 = nd.multi_sgd_mom_update(
        w, g, m, nd.array(np.array([0.1], np.float32)),
        nd.array(np.array([0.0], np.float32)), momentum=0.9, num_weights=1)
    assert outs2[0].dtype == w.dtype and outs2[1].dtype == m.dtype


def test_lars_update_matches_oracle():
    """lars_update (reference optimizer_op.cc lars_* family): trust-ratio
    scaled momentum SGD, zero-norm fallback to ratio 1."""
    r = np.random.RandomState(1)
    w = r.randn(8).astype(np.float32)
    g = r.randn(8).astype(np.float32)
    m = r.randn(8).astype(np.float32) * 0.1
    wn, mn = nd.lars_update(nd.array(w), nd.array(g), nd.array(m),
                            lr=0.2, momentum=0.9, eta=0.01, wd=0.001)
    wnorm = np.linalg.norm(w)
    gnorm = np.linalg.norm(g)
    trust = wnorm / (gnorm + 0.001 * wnorm + 1e-8)
    mref = 0.9 * m + 0.2 * 0.01 * trust * (g + 0.001 * w)
    np.testing.assert_allclose(mn.asnumpy(), mref, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(wn.asnumpy(), w - mref, rtol=1e-5, atol=1e-6)
    # zero weight -> trust ratio 1 (no div-by-zero blowup)
    w0 = np.zeros(4, np.float32)
    wn0, _ = nd.lars_update(nd.array(w0), nd.array(np.ones(4, np.float32)),
                            nd.array(np.zeros(4, np.float32)), lr=0.1,
                            momentum=0.0, eta=0.5)
    # reference guard: zero norms -> PLAIN lr (eta only inside the ratio)
    np.testing.assert_allclose(wn0.asnumpy(), -0.1 * np.ones(4), rtol=1e-6)


def test_lars_optimizer_trains():
    from mxnet_tpu import gluon
    mx.random.seed(2)
    net = gluon.nn.Dense(1, in_units=4)
    net.initialize(mx.initializer.Normal(0.2))
    tr = gluon.Trainer(net.collect_params(), "lars",
                       {"learning_rate": 1.0, "eta": 0.1, "momentum": 0.9})
    lf = gluon.loss.L2Loss()
    r = np.random.RandomState(0)
    X = r.randn(32, 4).astype(np.float32)
    Y = (X @ r.randn(4, 1)).astype(np.float32)
    losses = []
    for _ in range(25):
        with autograd.record():
            loss = lf(net(mx.nd.array(X)), mx.nd.array(Y))
        loss.backward()
        tr.step(32)
        losses.append(float(loss.mean().asnumpy()))
    assert losses[-1] < 0.5 * losses[0]


def test_reference_camelcase_aliases():
    """Upstream exposes legacy CamelCase op names alongside snake_case —
    both must resolve to the same kernels."""
    x = nd.array(np.arange(6, dtype=np.float32).reshape(2, 3))
    np.testing.assert_allclose(nd.SwapAxis(x, dim1=0, dim2=1).asnumpy(),
                               x.asnumpy().T)
    np.testing.assert_allclose(nd.Reshape(x, shape=(3, 2)).asnumpy(),
                               x.asnumpy().reshape(3, 2))
    np.testing.assert_allclose(nd.Flatten(x).asnumpy(), x.asnumpy())
    np.testing.assert_allclose(
        nd.Concat(x, x, dim=0).asnumpy(),
        np.concatenate([x.asnumpy()] * 2, axis=0))
    np.testing.assert_allclose(
        nd.logical_xor(nd.array(np.array([0., 1., 1.])),
                       nd.array(np.array([1., 1., 0.]))).asnumpy(),
        [1.0, 0.0, 1.0])
    seq = nd.SequenceMask(
        nd.array(np.ones((3, 2, 2), np.float32)),
        nd.array(np.array([1., 2.])),
        use_sequence_length=True)
    assert seq.asnumpy()[2, 0].sum() == 0.0   # masked beyond length


def test_lars_skips_trust_for_bias_gamma_beta():
    """Reference LARS excludes bias/gamma/beta from layer adaptation:
    those params update with plain momentum SGD."""
    opt = mx.optimizer.create("lars", learning_rate=0.5, momentum=0.0,
                              eta=0.001,
                              param_idx2name={0: "fc_weight", 1: "fc_bias"})
    w = np.ones(4, np.float32)
    g = np.full(4, 0.2, np.float32)
    wt = nd.array(w)
    opt.update(1, wt, nd.array(g), opt.create_state(1, wt))
    # plain sgd: w - lr*g (no tiny-eta trust scaling)
    np.testing.assert_allclose(wt.asnumpy(), w - 0.5 * g, rtol=1e-6)
    wt2 = nd.array(w)
    opt.update(0, wt2, nd.array(g), opt.create_state(0, wt2))
    assert not np.allclose(wt2.asnumpy(), w - 0.5 * g)   # trust applied


def test_lars_trainer_excludes_bias(seeded):
    """The bias exclusion must work through the PRIMARY path — gluon
    Trainer populates param_dict, not idx2name (review regression)."""
    from mxnet_tpu import gluon
    mx.random.seed(4)
    net = gluon.nn.Dense(2, in_units=3)
    net.initialize(mx.initializer.Normal(0.3))
    tr = gluon.Trainer(net.collect_params(), "lars",
                       {"learning_rate": 0.5, "momentum": 0.0,
                        "eta": 0.001})
    lf = gluon.loss.L2Loss()
    x = mx.nd.array(np.ones((4, 3), np.float32))
    y = mx.nd.array(np.zeros((4, 2), np.float32))
    b0 = net.bias.data().asnumpy().copy()
    with autograd.record():
        loss = lf(net(x), y)
    loss.backward()
    gb = net.bias.grad().asnumpy().copy()
    tr.step(1)
    # bias updated with PLAIN lr (trust forced to 1), i.e. -lr * grad,
    # not the ~1000x smaller eta-scaled step
    np.testing.assert_allclose(net.bias.data().asnumpy(), b0 - 0.5 * gb,
                               rtol=1e-4, atol=1e-6)
