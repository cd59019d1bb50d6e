"""Bucketed gradient fusion (kvstore/fusion.py, ISSUE 2).

The contract under test: ``pushpull_list`` with fusion enabled is
BIT-identical to the per-key push+pull loop — multi-replica, mixed dtypes
(separate buckets per dtype), odd sizes, key gaps from ``grad_req='null'``
params, and per-key fallback for sparse / compressed / update-on-kvstore
keys — while steady-state steps reuse cached plans and executables
(no retraces after step one).
"""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.kvstore.fusion import GradBucketer


def _make_values(shapes, dtypes, n_rep, seed=0):
    rng = np.random.RandomState(seed)
    vals = []
    for s, dt in zip(shapes, dtypes):
        reps = [nd.array(rng.standard_normal(s).astype(dt), ctx=mx.cpu(r))
                for r in range(n_rep)]
        vals.append(reps if n_rep > 1 else reps[0])
    return vals


def _run_pushpull(bucket_mb, keys, shapes, dtypes, vals, kv_type="local"):
    kv = mx.kv.create(kv_type)
    kv.set_bucket_size(bucket_mb)
    for k, s, dt in zip(keys, shapes, dtypes):
        kv.init(k, nd.zeros(s, dtype=dt))
    n_rep = len(vals[0]) if isinstance(vals[0], list) else 1
    outs = [[nd.zeros(s, dtype=dt, ctx=mx.cpu(r)) for r in range(n_rep)]
            if n_rep > 1 else nd.zeros(s, dtype=dt)
            for s, dt in zip(shapes, dtypes)]
    kv.pushpull_list(keys, vals, outs)
    return kv, outs


def _assert_bit_identical(outs_a, outs_b):
    for j, (a, b) in enumerate(zip(outs_a, outs_b)):
        la = a if isinstance(a, list) else [a]
        lb = b if isinstance(b, list) else [b]
        for r, (x, y) in enumerate(zip(la, lb)):
            xa, ya = x.asnumpy(), y.asnumpy()
            assert xa.dtype == ya.dtype
            assert np.array_equal(xa, ya), (j, r)


# ---------------------------------------------------------------------------
# bucket planning
# ---------------------------------------------------------------------------

def test_bucketer_plan_splits_by_size_and_dtype():
    b = GradBucketer(bucket_bytes=100)  # tiny bound to force splits
    sig = (
        ((10,), "float32", 1),   # 40 B
        ((10,), "float32", 1),   # 40 B  -> fits (80)
        ((10,), "float32", 1),   # 40 B  -> would be 120: new bucket
        ((10,), "float16", 1),   # different dtype: own bucket group
        ((100,), "float32", 1),  # 400 B oversized: own bucket
    )
    buckets = b.plan(sig)
    groups = [tuple(bk.positions) for bk in buckets]
    assert groups == [(0, 1), (2,), (3,), (4,)]
    assert b.plan(sig) is buckets  # cached plan object


def test_bucketer_plan_groups_by_replica_count():
    b = GradBucketer(bucket_bytes=1 << 20)
    sig = (((4,), "float32", 2), ((4,), "float32", 1), ((4,), "float32", 2))
    buckets = b.plan(sig)
    assert [tuple(bk.positions) for bk in buckets] == [(0, 2), (1,)]


# ---------------------------------------------------------------------------
# numerics: fused == per-key, bitwise
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n_rep", [1, 2, 4])
def test_fused_bit_identical_multi_replica(n_rep):
    shapes = [(3, 5), (7,), (11, 3), (1,), (2, 2, 3)]
    dtypes = ["float32"] * 5
    keys = [0, 1, 3, 4, 7]  # gaps: grad_req='null' params drop out of the list
    vals = _make_values(shapes, dtypes, n_rep)
    _, fused = _run_pushpull(25, keys, shapes, dtypes, vals)
    _, perkey = _run_pushpull(0, keys, shapes, dtypes, vals)
    _assert_bit_identical(fused, perkey)


def test_fused_bit_identical_mixed_dtypes_multiple_buckets():
    # interleaved dtypes + a tiny bucket bound: several buckets per dtype
    shapes = [(64,), (32,), (64,), (128,), (16,), (33,)]
    dtypes = ["float32", "float16", "float32", "float16", "float32",
              "float32"]
    keys = list(range(6))
    vals = _make_values(shapes, dtypes, n_rep=2)
    kv, fused = _run_pushpull(256 / (1 << 20), keys, shapes, dtypes, vals)
    _, perkey = _run_pushpull(0, keys, shapes, dtypes, vals)
    _assert_bit_identical(fused, perkey)
    sig = tuple((tuple(s), dt, 2) for s, dt in zip(shapes, dtypes))
    assert len(kv._bucketer.plan(sig)) > 2  # the bound actually split


def test_fused_updates_store_like_per_key():
    shapes, dtypes, keys = [(4,), (6,)], ["float32"] * 2, [0, 1]
    vals = _make_values(shapes, dtypes, n_rep=2)
    kv_f, _ = _run_pushpull(25, keys, shapes, dtypes, vals)
    kv_p, _ = _run_pushpull(0, keys, shapes, dtypes, vals)
    for k in keys:
        # a later plain pull must see the reduced value either way
        of = nd.zeros(shapes[k], dtype=dtypes[k])
        op = nd.zeros(shapes[k], dtype=dtypes[k])
        kv_f.pull(k, of)
        kv_p.pull(k, op)
        assert np.array_equal(of.asnumpy(), op.asnumpy())


def test_fused_dist_store_single_process():
    shapes, dtypes = [(5,), (3, 3)], ["float32"] * 2
    keys = [0, 1]
    vals = _make_values(shapes, dtypes, n_rep=2)
    _, fused = _run_pushpull(25, keys, shapes, dtypes, vals, "dist_tpu_sync")
    _, perkey = _run_pushpull(0, keys, shapes, dtypes, vals, "dist_tpu_sync")
    _assert_bit_identical(fused, perkey)


# ---------------------------------------------------------------------------
# fallback rules
# ---------------------------------------------------------------------------

def test_sparse_key_falls_back_per_key():
    from mxnet_tpu.ndarray import sparse as sp
    kv = mx.kv.create("local")
    kv.set_bucket_size(25)
    dense = nd.array(np.arange(12, dtype=np.float32).reshape(3, 4))
    rsp = sp.cast_storage(dense, "row_sparse")
    kv.init(0, nd.zeros((4,)))
    kv.init(1, rsp)          # sparse stored value
    kv.init(2, nd.zeros((2,)))
    v0 = nd.array(np.ones(4, np.float32))
    v2 = nd.array(np.full(2, 3.0, np.float32))
    o0, o2 = nd.zeros((4,)), nd.zeros((2,))
    o1 = nd.zeros((3, 4))
    kv.pushpull_list([0, 1, 2], [v0, dense, v2], [o0, o1, o2])
    np.testing.assert_array_equal(o0.asnumpy(), np.ones(4))
    np.testing.assert_array_equal(o1.asnumpy(), dense.asnumpy())
    np.testing.assert_array_equal(o2.asnumpy(), np.full(2, 3.0))


def test_compression_falls_back_whole_list():
    keys, shapes, dtypes = [0, 1], [(8,), (6,)], ["float32"] * 2
    vals = _make_values(shapes, dtypes, n_rep=2)

    def run(bucket_mb):
        kv = mx.kv.create("local")
        kv.set_bucket_size(bucket_mb)
        kv.set_gradient_compression({"type": "2bit", "threshold": 0.5})
        for k, s in zip(keys, shapes):
            kv.init(k, nd.zeros(s))
        outs = [nd.zeros(s) for s in shapes]
        kv.pushpull_list(keys, vals, outs)
        assert kv._bucketer is None  # compressed keys never built buckets
        return outs

    _assert_bit_identical(run(25), run(0))


def test_update_on_kvstore_falls_back():
    kv = mx.kv.create("local")
    kv.set_bucket_size(25)
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=1.0, rescale_grad=1.0))
    w = nd.array(np.full(4, 10.0, np.float32))
    kv.init(0, w)
    grad = nd.array(np.ones(4, np.float32))
    out = nd.zeros((4,))
    kv.pushpull_list([0], [grad], [out])
    # the store ran SGD: w - lr*grad = 9, proving the per-key updater path
    np.testing.assert_allclose(out.asnumpy(), np.full(4, 9.0))
    assert kv._bucketer is None


def test_bucket_mb_zero_disables_fusion():
    keys, shapes, dtypes = [0, 1], [(4,), (5,)], ["float32"] * 2
    vals = _make_values(shapes, dtypes, n_rep=1)
    kv, outs = _run_pushpull(0, keys, shapes, dtypes, vals)
    assert kv._bucketer is None
    _, perkey = _run_pushpull(0, keys, shapes, dtypes, vals)
    _assert_bit_identical(outs, perkey)


# ---------------------------------------------------------------------------
# retrace / cache behavior
# ---------------------------------------------------------------------------

def test_steady_state_reuses_cached_executables():
    shapes = [(3, 5), (7,), (16,)]
    dtypes = ["float32", "float32", "float16"]
    keys = [0, 1, 2]
    kv = mx.kv.create("local")
    kv.set_bucket_size(25)
    for k, s, dt in zip(keys, shapes, dtypes):
        kv.init(k, nd.zeros(s, dtype=dt))
    outs = [nd.zeros(s, dtype=dt) for s, dt in zip(shapes, dtypes)]
    for step in range(4):
        vals = _make_values(shapes, dtypes, n_rep=2, seed=step)
        kv.pushpull_list(keys, vals, outs)
        if step == 0:
            builds_after_first = kv._bucketer.builds
            assert builds_after_first > 0
    assert kv._bucketer.builds == builds_after_first
    assert len(kv._bucketer._plan_cache) == 1
    # the jitted executables themselves compiled exactly once each
    for fn in kv._bucketer._reduce_keys_cache.values():
        assert fn._cache_size() == 1


def test_set_bucket_size_resets_plans():
    keys, shapes, dtypes = [0, 1], [(4,), (5,)], ["float32"] * 2
    vals = _make_values(shapes, dtypes, n_rep=2)
    kv, _ = _run_pushpull(25, keys, shapes, dtypes, vals)
    assert kv._bucketer is not None
    kv.set_bucket_size(1)
    assert kv._bucketer is None  # stale plans dropped with the old bound


# ---------------------------------------------------------------------------
# trainer integration + telemetry
# ---------------------------------------------------------------------------

def _train(bucket_mb, n_ctx=2, steps=3):
    np.random.seed(0)
    mx.random.seed(0)
    net = gluon.nn.Sequential()
    net.add(gluon.nn.Dense(16, activation="relu"), gluon.nn.Dense(4))
    net.initialize(ctx=[mx.cpu(i) for i in range(n_ctx)])
    # a grad_req='null' param in the middle of the key sequence
    list(net.collect_params().values())[1].grad_req = "null"
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})
    xs = [nd.array(np.random.randn(8, 10).astype("float32"), ctx=mx.cpu(i))
          for i in range(n_ctx)]
    for _ in range(steps):
        for x in xs:
            with autograd.record():
                loss = (net(x) ** 2).sum()
            loss.backward()
        tr._init_kvstore()
        tr._kvstore.set_bucket_size(bucket_mb)
        tr.step(8)
    return [p.data().asnumpy() for p in net.collect_params().values()], tr


def test_trainer_fused_bit_identical_to_per_key():
    fused, tr = _train(25)
    perkey, _ = _train(0)
    for a, b in zip(fused, perkey):
        assert np.array_equal(a, b)
    bucketer = tr._kvstore._bucketer
    assert bucketer is not None and bucketer.builds > 0
    assert len(bucketer._plan_cache) == 1  # steady-state: one signature


def test_fused_telemetry_metrics():
    from mxnet_tpu import telemetry
    telemetry.enable()
    try:
        telemetry.REGISTRY.reset()
        keys, shapes, dtypes = [0, 1, 2], [(4,), (5,), (6,)], ["float32"] * 3
        vals = _make_values(shapes, dtypes, n_rep=2)
        _run_pushpull(25, keys, shapes, dtypes, vals)
        assert telemetry.counter(
            "mxnet_kvstore_fused_pushpulls_total").value == 1
        assert telemetry.counter(
            "mxnet_kvstore_fused_buckets_total").value == 1
        assert telemetry.counter("mxnet_kvstore_fused_keys_total").value == 3
        nbytes = sum(4 * int(np.prod(s)) for s in shapes) * 2
        assert telemetry.counter(
            "mxnet_kvstore_fused_bytes_total").value == nbytes
        assert telemetry.histogram(
            "mxnet_kvstore_fused_bucket_seconds").count == 1
        text = telemetry.to_prometheus()
        assert "mxnet_kvstore_fused_buckets_total" in text
    finally:
        telemetry.disable()
        telemetry.clear()


def test_trainer_fused_pushpull_counts():
    """A trainer's gradient set through the fused pushpull: 22 tensors (an
    embedding, two layers of four square weights, two ffn weights and four
    vectors, a head), 2 replicas each, one 25 MB bucket.  One warm step and
    two steady ones: one fused pushpull and one bucket a step, every key
    fused, nothing through the per-key push/pull, nothing built once warm."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.analysis.runtime import no_retrace
    shapes = [(256, 64)]
    for _ in range(2):
        shapes += [(64, 64)] * 4 + [(64, 256), (256, 64)] + [(64,)] * 4
    shapes += [(64, 256)]
    assert len(shapes) == 22
    dtypes = ["float32"] * len(shapes)
    keys = [f"w{i}" for i in range(len(shapes))]
    kv = mx.kv.create("local")
    kv.set_bucket_size(25)
    for k, s in zip(keys, shapes):
        kv.init(k, nd.zeros(s))
    vals = _make_values(shapes, dtypes, n_rep=2)
    outs = [nd.zeros(s) for s in shapes]
    telemetry.enable()
    try:
        telemetry.REGISTRY.reset()
        kv.pushpull_list(keys, vals, outs)
        outs[0].asnumpy()
        with no_retrace():
            for _ in range(2):
                kv.pushpull_list(keys, vals, outs)
            outs[0].asnumpy()
        counts = {name: telemetry.counter(f"mxnet_kvstore_{name}_total").value
                  for name in ("fused_pushpulls", "fused_buckets",
                               "fused_keys", "push_bytes", "pull_bytes")}
        assert counts == {"fused_pushpulls": 3, "fused_buckets": 3,
                          "fused_keys": 66, "push_bytes": 0, "pull_bytes": 0}
    finally:
        telemetry.disable()
        telemetry.clear()
