"""Sparse-storage kernel ops (reference src/operator/tensor/dot.cc
FComputeEx sparse paths, square_sum.cc, sparse_retain.cc — SURVEY §2.2
tensor/).

TPU-native storage dispatch: the reference routes (stype...) tuples to
FComputeEx kernels at graph-build time; here the sparse containers
(`ndarray/sparse.py`) are pairs of DENSE component tensors and these
registry ops are the kernels over those components — gather / scatter /
segment-sum that XLA tiles natively.  Static shapes throughout: the row
id of each csr element comes from a searchsorted over indptr (not a
data-dependent repeat), so everything jits.

Being ordinary registry ops they are differentiable (vjp-at-dispatch
flows into the `data` components and the dense operands) and reachable
from BOTH `mx.nd` and `mx.sym` — symbol programs carry the component
tensors as inputs, which is this framework's statement of the
reference's storage-type inference (the storage "type" is the choice of
component layout, fixed at build time, not a runtime tag).

The user-facing wrappers over the sparse CONTAINERS live in
`ndarray/sparse.py` (`mx.nd.sparse.dot/square_sum/sparse_retain`).
"""

from __future__ import annotations

from .registry import register


def _jnp():
    import jax.numpy as jnp
    return jnp


def _csr_rows(indptr, nnz):
    """Row id per csr element: r s.t. indptr[r] <= k < indptr[r+1]."""
    jnp = _jnp()
    k = jnp.arange(nnz, dtype=indptr.dtype)
    return jnp.searchsorted(indptr, k, side="right").astype(jnp.int32) - 1


@register("_sparse_dot_csr")
def _sparse_dot_csr(data, indptr, indices, rhs, transpose_a=False,
                    num_cols=0):
    """csr(lhs) @ dense(rhs) (or csr.T @ dense with ``transpose_a``) —
    lowers to gather + segment-sum, the TPU-friendly SpMM.

    data (nnz,), indptr (n_rows+1,), indices (nnz,), rhs (n_cols, k) for
    the plain product / (n_rows, k) for the transposed one.  ``num_cols``
    (static) is the csr's column count — needed for the transposed output
    shape.  Differentiable in data and rhs.
    """
    import jax
    jnp = _jnp()
    nnz = data.shape[0]
    n_rows = indptr.shape[0] - 1
    rows = _csr_rows(indptr.astype(jnp.int32), nnz)
    cols = indices.astype(jnp.int32)
    if not transpose_a:
        # out[r] = sum_k data[k] * rhs[indices[k]]  for k in row r
        gathered = rhs[cols] * data[:, None]
        return jax.ops.segment_sum(gathered, rows, num_segments=n_rows)
    # out[c] = sum_k data[k] * rhs[rows[k]]  for k with indices[k] == c
    if not num_cols:
        raise ValueError("_sparse_dot_csr(transpose_a=True) needs the "
                         "static num_cols attr (csr column count)")
    gathered = rhs[rows] * data[:, None]
    return jax.ops.segment_sum(gathered, cols, num_segments=int(num_cols))


@register("_square_sum_rs")
def _square_sum_rs(data, indices, num_rows=0, axis=None, keepdims=False):
    """square_sum over a row_sparse array (reference square_sum.cc — the
    lazy-update optimizers' helper): sum(x**2) over all/axis elements
    touching only stored rows.

    data (n_stored, dim), indices (n_stored,); num_rows static = full
    row count.  axis None -> scalar; 1 -> per-row (dense (num_rows,));
    0 -> per-column (dense (dim,)).
    """
    import jax
    jnp = _jnp()
    # accumulate in the input dtype when it is already >= f32 (x64 parity:
    # float64 inputs must not silently degrade), f32 for half dtypes
    acc_dt = data.dtype if data.dtype in (jnp.dtype(jnp.float32),
                                          jnp.dtype(jnp.float64)) \
        else jnp.float32
    sq = data.astype(acc_dt) ** 2
    if axis is None:
        out = jnp.sum(sq)
        return out.reshape((1,) * data.ndim) if keepdims else out
    axis = int(axis)
    if axis in (1, -1):
        if not num_rows:
            raise ValueError("_square_sum_rs(axis=1) needs num_rows")
        per_stored = jnp.sum(sq, axis=1)
        out = jnp.zeros((int(num_rows),), acc_dt) \
            .at[indices.astype(jnp.int32)].add(per_stored)
        return out[:, None] if keepdims else out
    if axis == 0:
        out = jnp.sum(sq, axis=0)
        return out[None, :] if keepdims else out
    raise ValueError(f"square_sum: unsupported axis {axis}")


@register("_sparse_retain_values")
def _sparse_retain_values(data, indices, row_ids):
    """Value/index masking core of sparse_retain (reference
    sparse_retain.cc): rows of ``data`` whose index is NOT in ``row_ids``
    are zeroed (static shapes: the container keeps nnz slots; dropping
    the zero rows is the wrapper's host-side compaction).  Differentiable
    in data (mask-gated identity)."""
    jnp = _jnp()
    mask = jnp.isin(indices, row_ids.astype(indices.dtype))
    return data * mask[:, None].astype(data.dtype)


@register("contrib.getnnz", differentiable=False)
def _getnnz(data, axis=None):
    """Count stored non-zeros (reference contrib getnnz for CSR; here the
    dense analog counts actual non-zeros — the storage classes report
    their stored length directly)."""
    jnp = _jnp()
    return jnp.sum((data != 0).astype(jnp.int64), axis=axis)
