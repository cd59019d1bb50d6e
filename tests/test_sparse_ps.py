"""Sparse parameter-server path tests (reference
tests/nightly/dist_sync_kvstore.py row_sparse cases + sparse optimizer
lazy-update semantics)."""

import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu.base import MXNetError
from mxnet_tpu.kvstore.sparse_ps import SparsePS
from mxnet_tpu.ndarray.sparse import RowSparseNDArray, cast_storage


def _rsp(values, rows, shape):
    return RowSparseNDArray(mx.nd.array(np.asarray(values, np.float32)),
                            mx.nd.array(np.asarray(rows, np.int64)), shape)


def test_ps_init_push_pull_exact():
    ps = SparsePS()
    ps.init("emb", mx.nd.zeros((10, 2)))
    # no optimizer: raw accumulation
    ps.push("emb", _rsp([[1, 1], [2, 2]], [3, 7], (10, 2)))
    out = ps.row_sparse_pull("emb", mx.nd.array([3, 7, 5]))
    np.testing.assert_array_equal(out.indices.asnumpy(), [3, 5, 7])
    dense = ps.pull_dense("emb").asnumpy()
    np.testing.assert_array_equal(dense[3], 1.0)
    np.testing.assert_array_equal(dense[7], 2.0)
    np.testing.assert_array_equal(dense[5], 0.0)


def test_ps_duplicate_rows_aggregate():
    ps = SparsePS()
    ps.init("t", mx.nd.zeros((6, 1)))
    ps.push("t", _rsp([[1], [2], [4]], [2, 2, 5], (6, 1)))
    dense = ps.pull_dense("t").asnumpy()
    assert dense[2, 0] == 3.0  # merged duplicates (reference merge buffer)
    assert dense[5, 0] == 4.0


def test_ps_server_side_sgd_lazy():
    # optimizer runs server-side on touched rows ONLY (lazy update)
    ps = SparsePS()
    ps.init("w", mx.nd.array(np.ones((8, 2), np.float32)))
    ps.set_optimizer(mx.optimizer.SGD(learning_rate=0.5, rescale_grad=1.0))
    ps.push("w", _rsp([[2, 2]], [1], (8, 2)))
    dense = ps.pull_dense("w").asnumpy()
    np.testing.assert_allclose(dense[1], 0.0)   # 1 - 0.5*2
    np.testing.assert_allclose(dense[0], 1.0)   # untouched rows unchanged
    np.testing.assert_allclose(dense[7], 1.0)


def test_ps_server_side_adagrad_state_per_row():
    # adaptive optimizer state must persist per row across pushes
    ps = SparsePS()
    ps.init("w", mx.nd.zeros((4, 1)))
    ps.set_optimizer(mx.optimizer.AdaGrad(learning_rate=1.0, eps=1e-8))
    g = _rsp([[1.0]], [2], (4, 1))
    ps.push("w", g)
    after1 = ps.pull_dense("w").asnumpy()[2, 0]
    ps.push("w", g)
    after2 = ps.pull_dense("w").asnumpy()[2, 0]
    # adagrad: first step ≈ -1.0, second smaller (state accumulated)
    np.testing.assert_allclose(after1, -1.0, rtol=1e-4)
    assert abs(after2 - after1) < 1.0  # second step shrank
    assert abs(after2 - after1) > 0.1
    # rows never pushed keep zero state and value
    assert ps.pull_dense("w").asnumpy()[0, 0] == 0.0


def test_dist_kvstore_routes_sparse_keys():
    kv = mx.kv.create("dist_tpu_sync")
    kv.init("emb", cast_storage(mx.nd.zeros((12, 3)), "row_sparse"))
    kv.init(0, mx.nd.ones((4,)))  # dense key still works alongside
    kv.set_optimizer(mx.optimizer.SGD(learning_rate=1.0, rescale_grad=1.0))
    kv.push("emb", _rsp([[1, 1, 1]], [4], (12, 3)))
    out = kv.row_sparse_pull("emb", row_ids=mx.nd.array([4, 6]))
    np.testing.assert_allclose(out.data.asnumpy()[0], -1.0)  # sgd applied
    np.testing.assert_allclose(out.data.asnumpy()[1], 0.0)
    # dense pull of the sparse table
    dense = mx.nd.zeros((12, 3))
    kv.pull("emb", dense)
    np.testing.assert_allclose(dense.asnumpy()[4], -1.0)


def test_dist_push_aggregates_replicas_before_update():
    # two replica grads must produce ONE stateful-optimizer step on the
    # merged grad (reference aggregate-then-update), not two
    kv = mx.kv.create("dist_tpu_sync")
    kv.init("w", cast_storage(mx.nd.zeros((4, 1)), "row_sparse"))
    kv.set_optimizer(mx.optimizer.AdaGrad(learning_rate=1.0, eps=1e-8))
    g1 = _rsp([[0.5]], [2], (4, 1))
    g2 = _rsp([[0.5]], [2], (4, 1))
    kv.push("w", [g1, g2])
    dense = mx.nd.zeros((4, 1))
    kv.pull("w", dense)
    # merged grad 1.0 → one adagrad step of -1.0 (two 0.5-steps ≈ -1.71)
    np.testing.assert_allclose(dense.asnumpy()[2, 0], -1.0, rtol=1e-4)


def test_dist_sparse_list_key_forms():
    kv = mx.kv.create("dist_tpu_sync")
    kv.init(["emb"], [cast_storage(mx.nd.zeros((6, 2)), "row_sparse")])
    kv.push(["emb"], [_rsp([[1, 1]], [3], (6, 2))])
    out = mx.nd.zeros((6, 2))
    kv.pull(["emb"], [out])
    np.testing.assert_allclose(out.asnumpy()[3], 1.0)
    # per-out row_ids honored
    o1 = cast_storage(mx.nd.zeros((6, 2)), "row_sparse")
    o2 = cast_storage(mx.nd.zeros((6, 2)), "row_sparse")
    kv.row_sparse_pull("emb", out=[o1, o2],
                       row_ids=[mx.nd.array([3]), mx.nd.array([0, 3])])
    assert o1.indices.asnumpy().tolist() == [3]
    assert o2.indices.asnumpy().tolist() == [0, 3]


def test_ps_multi_precision_master_weights_init_from_rows():
    # first-touch state init runs create_state on the CURRENT row values:
    # an fp32 master-weight leaf must start at the row values, not zeros
    import ml_dtypes
    ps = SparsePS()
    table = np.full((4, 2), 2.0, np.float32).astype(ml_dtypes.bfloat16)
    ps.init("w", mx.nd.array(table))
    ps.set_optimizer(mx.optimizer.SGD(learning_rate=0.25, rescale_grad=1.0,
                                      multi_precision=True))
    g = RowSparseNDArray(mx.nd.array(np.ones((1, 2), np.float32)
                                     .astype(ml_dtypes.bfloat16)),
                         mx.nd.array([1]), (4, 2))
    ps.push("w", g)
    dense = ps.pull_dense("w").asnumpy().astype(np.float32)
    # master starts at 2.0 → 2.0 - 0.25*1 = 1.75 (zero master gives -0.25)
    np.testing.assert_allclose(dense[1], 1.75)
    np.testing.assert_allclose(dense[0], 2.0)


def test_ps_set_optimizer_resets_state():
    ps = SparsePS()
    ps.init("w", mx.nd.zeros((3, 1)))
    ps.set_optimizer(mx.optimizer.SGD(learning_rate=1.0, momentum=0.9,
                                      rescale_grad=1.0))
    g = _rsp([[1.0]], [0], (3, 1))
    ps.push("w", g)
    ps.push("w", g)  # momentum now non-zero for row 0
    ps.set_optimizer(mx.optimizer.AdaGrad(learning_rate=1.0, eps=1e-8))
    ps.push("w", g)
    tbl = ps._tables["w"]
    # adagrad history after ONE push must be g^2, not stale sgd momentum
    np.testing.assert_allclose(tbl.state_leaves[0][0], 1.0, rtol=1e-6)


def test_dist_pull_sparse_out_contract():
    kv = mx.kv.create("dist_tpu_sync")
    kv.init("e", cast_storage(mx.nd.ones((4, 2)), "row_sparse"))
    sparse_out = cast_storage(mx.nd.zeros((4, 2)), "row_sparse")
    kv.pull("e", sparse_out)  # ignore_sparse default: skipped, no crash
    with pytest.raises(MXNetError, match="row_sparse_pull"):
        kv.pull("e", sparse_out, ignore_sparse=False)


def test_ps_errors():
    ps = SparsePS()
    with pytest.raises(MXNetError, match="not initialized"):
        ps.push("nope", _rsp([[1]], [0], (2, 1)))
    ps.init("k", mx.nd.zeros((2, 1)))
    with pytest.raises(MXNetError, match="already"):
        ps.init("k", mx.nd.zeros((2, 1)))


@pytest.mark.slow  # >10s on the tier-1 budget clock (r7 audit); runs in the CI slow lane
def test_fm_example_trains():
    sys.path.insert(0, os.path.join(os.path.dirname(
        os.path.dirname(os.path.abspath(__file__))), "examples", "sparse"))
    try:
        import factorization_machine as fm
    finally:
        sys.path.pop(0)
    result, losses = fm.run(num_features=2000, batches=60, batch_size=128,
                            nnz=10, lr=0.2, log=False)
    assert result["loss_last"] < result["loss_first"], losses[:3]
    assert result["value"] > 0  # samples/sec reported