"""Registry-level sparse-storage op tests, mirroring
the reference's tests/python/unittest/test_sparse_operator.py patterns:
dense-oracle forward parity + numeric gradients through the recorded
tape.  Reference kernels: src/operator/tensor/dot.cc (FComputeEx csr
paths), square_sum.cc, sparse_retain.cc, indexing_op.cc (row_sparse
Embedding backward)."""

import numpy as np

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, nd
from mxnet_tpu.ndarray import sparse


def _rand_csr(r, m, n, density=0.3):
    d = r.randn(m, n).astype(np.float32)
    d[r.rand(m, n) > density] = 0.0
    return d, sparse.csr_matrix(d)


def test_csr_dot_forward_matches_dense(seeded):
    r = np.random.RandomState(0)
    d, csr = _rand_csr(r, 6, 9)
    rhs = nd.array(r.randn(9, 4).astype(np.float32))
    out = sparse.dot(csr, rhs)
    np.testing.assert_allclose(out.asnumpy(), d @ rhs.asnumpy(),
                               rtol=1e-5, atol=1e-5)


def test_csr_dot_transpose_forward(seeded):
    r = np.random.RandomState(1)
    d, csr = _rand_csr(r, 6, 9)
    rhs = nd.array(r.randn(6, 3).astype(np.float32))
    out = sparse.dot(csr, rhs, transpose_a=True)
    np.testing.assert_allclose(out.asnumpy(), d.T @ rhs.asnumpy(),
                               rtol=1e-5, atol=1e-5)


def test_csr_dot_grads(seeded):
    """d/drhs [csr @ rhs] == csr.T @ dout and d/dvalues flows to the
    stored elements — both through the recorded tape."""
    r = np.random.RandomState(2)
    d, csr = _rand_csr(r, 5, 7)
    rhs = nd.array(r.randn(7, 3).astype(np.float32))
    rhs.attach_grad()
    csr.data.attach_grad()
    w = nd.array(r.randn(5, 3).astype(np.float32))
    with autograd.record():
        out = sparse.dot(csr, rhs)
        loss = (out * w).sum()
    loss.backward()
    np.testing.assert_allclose(rhs.grad.asnumpy(), d.T @ w.asnumpy(),
                               rtol=1e-5, atol=1e-5)
    # grad wrt stored values: dL/ddata[k] = rhs[col_k] . w[row_k]
    rows, cols = np.nonzero(d)
    want = np.einsum("kj,kj->k", rhs.asnumpy()[cols], w.asnumpy()[rows])
    np.testing.assert_allclose(csr.data.grad.asnumpy(), want,
                               rtol=1e-5, atol=1e-5)


def test_square_sum_axes_and_grad(seeded):
    r = np.random.RandomState(3)
    dense = r.randn(8, 4).astype(np.float32)
    dense[[1, 3, 5, 6]] = 0.0
    rsp = sparse.row_sparse_array(dense)
    np.testing.assert_allclose(sparse.square_sum(rsp).asnumpy(),
                               (dense ** 2).sum(), rtol=1e-5)
    np.testing.assert_allclose(sparse.square_sum(rsp, axis=1).asnumpy(),
                               (dense ** 2).sum(1), rtol=1e-5)
    np.testing.assert_allclose(sparse.square_sum(rsp, axis=0).asnumpy(),
                               (dense ** 2).sum(0), rtol=1e-5)
    np.testing.assert_allclose(
        sparse.square_sum(rsp, axis=1, keepdims=True).asnumpy(),
        (dense ** 2).sum(1, keepdims=True), rtol=1e-5)
    # gradient: d/dx sum(x^2) = 2x on stored rows
    rsp.data.attach_grad()
    with autograd.record():
        loss = sparse.square_sum(rsp)
    loss.backward()
    np.testing.assert_allclose(rsp.data.grad.asnumpy(),
                               2 * rsp.data.asnumpy(), rtol=1e-5)


def test_sparse_retain_function(seeded):
    dense = np.zeros((6, 3), np.float32)
    dense[[0, 2, 4]] = np.arange(9, dtype=np.float32).reshape(3, 3) + 1
    rsp = sparse.row_sparse_array(dense)
    kept = sparse.sparse_retain(rsp, nd.array(np.array([2, 5])))
    out = kept.tostype("default").asnumpy()
    want = np.zeros_like(dense)
    want[2] = dense[2]
    np.testing.assert_allclose(out, want)
    # the registry masking kernel agrees with the container compaction
    masked = nd._sparse_retain_values(
        rsp.data, rsp.indices, nd.array(np.array([2, 5])))
    np.testing.assert_allclose(
        masked.asnumpy(),
        np.where(np.isin([0, 2, 4], [2, 5])[:, None],
                 rsp.data.asnumpy(), 0.0))


def test_embedding_sparse_grad_rowsparse_view(seeded):
    """Embedding(sparse_grad=True): param.grad() returns a row_sparse
    gradient carrying exactly the touched rows (reference indexing_op.cc
    SparseEmbedding backward contract)."""
    vocab, dim = 20, 4
    emb = gluon.nn.Embedding(vocab, dim, sparse_grad=True)
    emb.initialize(mx.initializer.Normal(0.5))
    tokens = nd.array(np.array([[3, 7, 3], [11, 7, 19]], np.float32))
    w = emb.weight
    assert w.grad_stype == "row_sparse"
    with autograd.record():
        out = emb(tokens)
        loss = (out * out).sum()
    loss.backward()
    g = w.grad()
    assert isinstance(g, sparse.RowSparseNDArray)
    touched = sorted(set(np.asarray(tokens.asnumpy(), np.int64).ravel()))
    assert sorted(g.indices.asnumpy().tolist()) == touched
    # values match the dense grad restricted to those rows
    dense_g = w.grad(stype="default").asnumpy()
    np.testing.assert_allclose(g.tostype("default").asnumpy(), dense_g,
                               rtol=1e-6)
    assert np.abs(dense_g[touched]).sum() > 0


def test_sparse_retain_grad_flows_to_values(seeded):
    """sparse_retain's value path rides differentiable registry ops
    (_sparse_retain_values + take): grads reach the stored rows."""
    dense = np.zeros((6, 3), np.float32)
    dense[[0, 2, 4]] = np.arange(9, dtype=np.float32).reshape(3, 3) + 1
    rsp = sparse.row_sparse_array(dense)
    rsp.data.attach_grad()
    with autograd.record():
        kept = sparse.sparse_retain(rsp, nd.array(np.array([2, 5])))
        loss = (kept.data * kept.data).sum()
    loss.backward()
    want = np.zeros_like(dense[[0, 2, 4]])
    want[1] = 2 * dense[2]
    np.testing.assert_allclose(rsp.data.grad.asnumpy(), want)
