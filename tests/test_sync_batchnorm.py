"""SyncBatchNorm contract tests (reference
src/operator/contrib/sync_batch_norm.cc + gluon.contrib SyncBatchNorm).

The absorption claim: under ``parallel.TrainStep`` (one SPMD program, the
batch axis global) plain BN statistics ARE the synchronized statistics —
GSPMD inserts the cross-device reduction.  Test 1 pins that: an 8-way
data-parallel TrainStep must produce bit-comparable running stats and
loss to the SAME model stepped on the full batch without a mesh.

Test 2 pins the DOCUMENTED divergence of the legacy replica path
(per-ctx eager forwards a la split_and_load): each replica folds its OWN
half-batch statistics into the running buffers sequentially — per-replica
stats, exactly what upstream plain BatchNorm would do per device.
"""

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd, parallel
from mxnet_tpu.gluon.contrib.nn import SyncBatchNorm


def _make_net(seed=3):
    mx.random.seed(seed)
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(6, in_units=5))
        net.add(SyncBatchNorm(in_channels=6, num_devices=8))
        net.add(gluon.nn.Dense(3, in_units=6))
    net.initialize(mx.initializer.Xavier())
    return net


def _stats(net):
    out = {}
    for name, p in net.collect_params().items():
        for key in ("running_mean", "running_var"):
            if key in name:
                out[key] = p.data().asnumpy().copy()
    return out


def test_trainstep_bn_stats_are_global_batch():
    """dp=8 TrainStep running stats == no-mesh full-batch stats."""
    r = np.random.RandomState(0)
    x = (r.randn(16, 5) * 2 + 1).astype(np.float32)
    y = r.randn(16, 3).astype(np.float32)

    def loss_fn(o, l):
        return ((o - l) ** 2).mean()

    results = {}
    for mode in ("sharded", "full"):
        import jax
        net = _make_net()
        mesh = parallel.make_mesh() if mode == "sharded" else \
            parallel.DeviceMesh(devices=jax.devices()[:1], shape=(1,),
                                axis_names=("dp",))
        if mode == "sharded":
            assert mesh.axis_size(mesh.axis_names[0]) == 8
        step = parallel.TrainStep(
            net, loss_fn, mx.optimizer.SGD(learning_rate=0.1), mesh=mesh,
            donate=False)
        loss = float(step(nd.array(x), nd.array(y)).asscalar())
        results[mode] = (loss, _stats(net))

    l_sh, st_sh = results["sharded"]
    l_full, st_full = results["full"]
    assert np.isfinite(l_sh)
    np.testing.assert_allclose(l_sh, l_full, rtol=1e-6)
    assert st_sh and sorted(st_sh) == sorted(st_full)
    for k in st_sh:
        np.testing.assert_allclose(st_sh[k], st_full[k], rtol=1e-5,
                                   atol=1e-6, err_msg=k)
    # and the stats really moved (the test would pass vacuously otherwise)
    assert not np.allclose(st_sh["running_mean"], 0.0)


def test_replica_path_keeps_per_replica_stats():
    """Eager per-ctx forwards (the split_and_load pattern) fold HALF-batch
    stats sequentially — the documented per-replica behavior."""
    r = np.random.RandomState(1)
    x = (r.randn(8, 5) * 3).astype(np.float32)
    halves = [x[:4], x[4:]]

    bn = SyncBatchNorm(in_channels=5, num_devices=2, momentum=0.9)
    bn.initialize()
    for h in halves:                      # replica forwards, in sequence
        with autograd.record():
            bn(nd.array(h))
    got = bn.params.get("running_mean").data().asnumpy()

    # oracle: sequential momentum updates with PER-HALF means
    want = np.zeros(5, np.float32)
    for h in halves:
        want = 0.9 * want + 0.1 * h.mean(axis=0)
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)

    # which is NOT the full-batch statistic — the divergence the docstring
    # warns about (use TrainStep when synchronized stats matter)
    full = 0.9 * (0.9 * np.zeros(5) + 0.1 * x.mean(axis=0)) \
        + 0.1 * x.mean(axis=0)
    assert not np.allclose(got, full, rtol=1e-3)
