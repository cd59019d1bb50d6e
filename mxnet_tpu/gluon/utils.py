"""gluon.utils (reference python/mxnet/gluon/utils.py): split_and_load,
clip_global_norm, download (gated — no egress in this environment), check_sha1."""

from __future__ import annotations

import functools
import os

from ..base import MXNetError
from .. import ndarray as nd
from ..ndarray.ndarray import NDArray

__all__ = ["split_data", "split_and_load", "clip_global_norm", "check_sha1",
           "download", "remat_call"]


def split_data(data, num_slice, batch_axis=0, even_split=True):
    size = data.shape[batch_axis]
    if even_split and size % num_slice != 0:
        raise MXNetError(
            f"data with shape {data.shape} cannot be evenly split into "
            f"{num_slice} slices along axis {batch_axis}")
    step = size // num_slice
    slices = []
    for i in range(num_slice):
        begin = i * step
        end = (i + 1) * step if i < num_slice - 1 else size
        slices.append(data.slice_axis(batch_axis, begin, end))
    return slices


def split_and_load(data, ctx_list, batch_axis=0, even_split=True):
    if not isinstance(data, NDArray):
        data = nd.array(data, ctx=ctx_list[0])
    if len(ctx_list) == 1:
        return [data.as_in_context(ctx_list[0])]
    slices = split_data(data, len(ctx_list), batch_axis, even_split)
    return [s.as_in_context(ctx) for s, ctx in zip(slices, ctx_list)]


def clip_global_norm(arrays, max_norm, check_isfinite=True):
    """Rescale arrays so the joint L2 norm ≤ max_norm (returns the norm)."""
    import jax.numpy as jnp
    import math
    total = None
    for a in arrays:
        s = jnp.sum(jnp.square(a._data))
        total = s if total is None else total + s
    norm = float(jnp.sqrt(total))
    if check_isfinite and not math.isfinite(norm):
        import warnings
        warnings.warn("nan or inf found in clip_global_norm", stacklevel=2)
    scale = max_norm / (norm + 1e-8)
    if scale < 1.0:
        for a in arrays:
            a._set_data(a._data * scale)
    return norm


def check_sha1(filename, sha1_hash):
    import hashlib
    sha1 = hashlib.sha1()
    with open(filename, "rb") as f:
        while True:
            data = f.read(1048576)
            if not data:
                break
            sha1.update(data)
    return sha1.hexdigest() == sha1_hash


def download(url, path=None, overwrite=False, sha1_hash=None,
             retries=5, verify_ssl=True):  # noqa: ARG001
    """Reference API; this environment has no network egress, so only a
    local cache hit can succeed."""
    fname = path if path and not os.path.isdir(path) else \
        os.path.join(path or ".", url.split("/")[-1])
    if os.path.exists(fname) and not overwrite and \
            (not sha1_hash or check_sha1(fname, sha1_hash)):
        return fname
    raise MXNetError(
        f"cannot download {url}: network egress is unavailable in this "
        f"environment and {fname} is not cached locally")


def remat_call(block, *inputs, keep=None):
    """Run ``block(*inputs)`` with activation REMATERIALIZATION: the
    block's internal activations are not stored for backward — they are
    recomputed from the block inputs during the gradient pass
    (``jax.checkpoint``).  This is the TPU-native analog of the
    reference's ``MXNET_BACKWARD_DO_MIRROR`` memory/compute trade
    (docs/faq/env_var.md): backward does ~1 extra forward of compute and
    activation memory drops from O(layers) to O(1) per wrapped segment —
    what makes long-sequence configs fit one chip (SURVEY §5.7).

    ``keep``: a ``jax.checkpoint`` policy naming what else the backward
    keeps (e.g. the products, ``jax.checkpoint_policies.
    dots_with_no_batch_dims_saveable``); what it keeps is not made again.
    ``None`` keeps the inputs and parameters alone.

    The whole block becomes ONE node on the autograd tape (its vjp is the
    checkpointed function's vjp), so it composes with ``autograd.record``
    / ``TrainStep`` like any fused op.  Blocks that MUTATE state in
    forward (BatchNorm running stats) are rejected — the mutation would
    silently vanish.

    Under recording a call grows ``mxnet_remat_applications_total{keep}``
    by one and ``mxnet_remat_kept_bytes_total{keep}`` by the bytes of the
    residuals its vjp holds beside the parameters (``keep`` = ``inputs``
    for ``None``, ``products`` for a policy); always on.

    What the block reports through ``parallel.report_counter`` while a
    ``TrainStep`` traces it leaves the checkpointed function as outputs of
    its own and is reported again outside it: collected once a traced
    forward, not again for the pass the backward makes.
    """
    import jax
    from .. import autograd, parallel
    from ..ndarray.ndarray import swap_slot_values
    from ..telemetry import metrics

    params = [p for _, p in sorted(block.collect_params().items())]
    in_ctx = next((a.ctx for a in inputs if isinstance(a, NDArray)), None)
    param_nds = [p.data(in_ctx) for p in params]
    arrays = [a._data for a in inputs] + [p._data for p in param_nds]
    n_in = len(inputs)
    train = autograd.is_training()
    mutated = [False]
    reported = []       # the keys of what the block reported, in order

    @functools.partial(jax.checkpoint, policy=keep)
    def f(*arrs):
        in_arr, p_arr = arrs[:n_in], arrs[n_in:]
        with swap_slot_values(zip(param_nds, p_arr)) as saved, \
                parallel._collect_reports(inner=True) as reports:
            in_nds = [NDArray._from_data(a) for a in in_arr]
            with autograd._scope(recording=False, training=train):
                out = block(*in_nds)
            if any(slot.value is not old and slot.value is not rep
                   for (slot, old), rep in zip(saved, p_arr)):
                mutated[0] = True
            if isinstance(out, (list, tuple)):
                raise MXNetError(
                    "remat_call supports single-output blocks")
        reported[:] = reports
        return out._data, [reports[key] for key in reported]

    if autograd.is_recording():
        out_raw, vjp_fn, values = jax.vjp(f, *arrays, has_aux=True)
        weights = {id(a) for a in arrays[n_in:]}
        label = {"keep": "inputs" if keep is None else "products"}
        metrics.counter(
            "mxnet_remat_applications_total",
            "Blocks run under gluon.utils.remat_call while recording.",
            labels=label).inc()
        metrics.counter(
            "mxnet_remat_kept_bytes_total",
            "Bytes of the residuals a remat_call's vjp holds beside the "
            "block's parameters.", labels=label).inc(sum(
                leaf.size * leaf.dtype.itemsize
                for leaf in jax.tree_util.tree_leaves(vjp_fn)
                if id(leaf) not in weights))
    else:
        (out_raw, values), vjp_fn = f(*arrays), None
    for (name, labels, kind), value in zip(reported, values):
        parallel.report_counter(name, value, dict(labels), kind)
    if mutated[0]:
        raise MXNetError(
            "remat_call: block mutates state in forward (BatchNorm "
            "running stats?) — rematerialization would re-run and then "
            "DROP the mutation; wrap only pure blocks")
    result = NDArray._from_data(out_raw, ctx=in_ctx)
    if vjp_fn is not None:
        # tape node with op=None (like autograd.Function): create_graph
        # backward then replays the stored vjp closure instead of trying
        # to re-dispatch a registry op that does not exist
        all_ins = list(inputs) + param_nds
        node = autograd._Node(
            "_remat_block", vjp_fn, autograd._entries_for(all_ins),
            [(result.shape, result.dtype)])
        autograd._st().tape.append(node)
        result._node = (node, 0)
    return result
