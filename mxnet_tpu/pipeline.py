"""Pipeline parallelism (GPipe-style) over a TPU mesh axis.

NEW capability relative to the reference: SURVEY §2.4 flags pipeline
parallelism ABSENT upstream (nothing beyond manual ``group2ctx`` placement +
engine async overlap — no GPipe/1F1B schedule anywhere).  The TPU-native
design follows the scaling-book recipe rather than any reference code:

 - the model's homogeneous trunk (e.g. transformer layers) is split into
   ``n_stages`` stages whose parameters are **stacked** along a leading
   stage dimension and sharded over a ``'pp'`` mesh axis — one stage per
   device group;
 - microbatches flow through the stages on a ``lax.scan`` schedule; stage
   boundaries are ``lax.ppermute`` shifts that ride ICI;
 - the whole schedule is a pure function, so ``jax.grad`` through it yields
   the reverse (backward) pipeline automatically — GPipe semantics
   (all-forward, all-backward) with XLA overlapping the bubble where it can;
 - combining with data parallelism is just a 2-D mesh ('dp','pp'): batch
   sharded over 'dp', stage params over 'pp'.

Embedding/head layers (whose activation shapes differ from the trunk's)
stay outside the pipelined region, exactly like megatron-style stacks.

The schedule: with S stages and M microbatches, tick t ∈ [0, S+M-1):
stage 0 feeds microbatch t (while t < M), stage s computes the activation
it received from stage s-1 at tick t-1, and stage S-1 emits the output for
microbatch t-(S-1).  Bubble fraction = (S-1)/(M+S-1), the GPipe bound.
"""

from __future__ import annotations

import numpy as _np

from .base import MXNetError

__all__ = ["gpipe", "pipeline_apply", "stack_blocks", "PipelinedBlock"]


def gpipe(stage_fn, n_stages, n_microbatches, mesh, axis="pp",
          data_axis=None):
    """Build the SPMD GPipe schedule for a homogeneous stage function.

    Parameters
    ----------
    stage_fn : callable ``(stage_params, activation) -> activation``
        One pipeline stage.  Must preserve the activation shape (pipeline
        the homogeneous trunk; put embedding/head outside).
    n_stages : int — must equal the mesh's ``axis`` size.
    n_microbatches : int — microbatches per call; the global batch dim must
        divide by it.
    mesh : DeviceMesh with a ``'pp'`` (or ``axis``) axis.
    axis : name of the pipeline mesh axis.
    data_axis : optional name of a data-parallel axis; when given, the
        activation batch dim is sharded over it as well.

    Returns
    -------
    ``fn(stacked_params, x) -> y`` — jit-compiled; ``stacked_params`` is a
    pytree whose leaves have leading dim ``n_stages`` (sharded over
    ``axis``), ``x`` the trunk input ``(batch, ...)``.  Differentiable.
    """
    import jax
    import jax.numpy as jnp

    if mesh.axis_size(axis) != n_stages:
        raise MXNetError(
            f"gpipe: mesh axis {axis!r} has size {mesh.axis_size(axis)}, "
            f"need n_stages={n_stages}")
    S, M = int(n_stages), int(n_microbatches)

    def schedule(params_stacked, x):
        # local views: leading stage dim is 1 on each pp group
        params = jax.tree_util.tree_map(lambda p: p[0], params_stacked)
        idx = jax.lax.axis_index(axis)
        b = x.shape[0]
        micro = x.reshape((M, b // M) + x.shape[1:])
        zero = jnp.zeros_like(micro[0])
        shift_perm = [(i, i + 1) for i in range(S - 1)]

        def tick(carry, t):
            state, outbuf = carry
            feed = jnp.where(t < M, micro[jnp.minimum(t, M - 1)], zero)
            inp = jnp.where(idx == 0, feed, state)
            y = stage_fn(params, inp)
            m = t - (S - 1)
            valid = jnp.logical_and(m >= 0, idx == S - 1)
            upd = jax.lax.dynamic_update_slice(
                outbuf, y[None].astype(outbuf.dtype),
                (jnp.maximum(m, 0),) + (0,) * y.ndim)
            outbuf = jnp.where(valid, upd, outbuf)
            if S > 1:
                state = jax.lax.ppermute(y, axis, shift_perm)
            else:
                state = y
            return (state, outbuf), None

        (_, outbuf), _ = jax.lax.scan(
            tick, (zero, jnp.zeros_like(micro)), jnp.arange(S + M - 1))
        # only the last stage wrote non-zeros; psum replicates the result
        # across the pipeline axis (grad of psum = identity broadcast)
        out = jax.lax.psum(outbuf, axis)
        return out.reshape(x.shape)

    P = jax.sharding.PartitionSpec
    stage_spec = P(axis)
    act_spec = P(data_axis) if data_axis else P()

    dp = mesh.axis_size(data_axis) if data_axis else 1

    def wrapped(params_stacked, x):
        # validate up front: a non-divisible batch otherwise fails deep
        # inside shard_map with an opaque jax reshape error
        bglobal = x.shape[0]
        if bglobal % dp != 0 or (bglobal // dp) % M != 0:
            raise MXNetError(
                f"gpipe: batch {bglobal} (/{dp} data-parallel shards -> "
                f"{bglobal // dp if bglobal % dp == 0 else bglobal}/shard) "
                f"must be divisible by n_microbatches={M}")
        in_specs = (jax.tree_util.tree_map(lambda _: stage_spec,
                                           params_stacked), act_spec)
        f = jax.shard_map(schedule, mesh=mesh.mesh, in_specs=in_specs,
                          out_specs=act_spec, check_vma=False)
        return f(params_stacked, x)

    return jax.jit(wrapped)


def pipeline_apply(stage_fn, stacked_params, x, mesh, n_microbatches=None,
                   axis="pp", data_axis=None):
    """One-shot convenience wrapper over :func:`gpipe` (builds + calls)."""
    import jax
    leaves = jax.tree_util.tree_leaves(stacked_params)
    n_stages = leaves[0].shape[0]
    if n_microbatches is None:
        n_microbatches = max(2 * n_stages, 1)
    fn = gpipe(stage_fn, n_stages, n_microbatches, mesh, axis=axis,
               data_axis=data_axis)
    return fn(stacked_params, x)


# --------------------------------------------------------------------------
# Gluon bridge: stack identically-structured blocks into one stage pytree
# --------------------------------------------------------------------------

def stack_blocks(blocks, probe):
    """Stack N identically-structured Gluon blocks into (stage_fn, params).

    ``blocks`` — a list of HybridBlocks with identical parameter structure
    (e.g. N transformer encoder cells).  ``probe`` — an example activation
    NDArray used to finish deferred shape inference.

    Returns ``(stage_fn, stacked)``: ``stacked`` is a dict name→jnp array
    with leading dim N; ``stage_fn(params, x)`` runs ONE stage functionally
    by temporarily pointing the template block's parameter slots at the
    traced values (the same slot-swap discipline TrainStep uses).
    """
    import jax.numpy as jnp
    from . import autograd
    from .ndarray.ndarray import NDArray

    template = blocks[0]
    with autograd.pause():
        for blk in blocks:
            blk(probe)  # deferred init
    names = list(template.collect_params().keys())
    per_block = []
    for blk in blocks:
        ps = blk.collect_params()
        ks = list(ps.keys())
        if len(ks) != len(names):
            raise MXNetError("stack_blocks: blocks differ in structure")
        per_block.append([ps[k].data()._data for k in ks])
    stacked = {
        name: jnp.stack([vals[i] for vals in per_block])
        for i, name in enumerate(names)}
    t_params = [template.collect_params()[k] for k in names]

    from .ndarray.ndarray import swap_slot_values

    def stage_fn(params, x):
        with swap_slot_values((p._data, params[name])
                              for p, name in zip(t_params, names)):
            out = template(NDArray._from_data(x))
            return out._data

    return stage_fn, stacked


class PipelinedBlock:
    """Pipeline-parallel wrapper for a homogeneous stack of Gluon blocks.

    ``PipelinedBlock(blocks, mesh, n_microbatches)`` shards the blocks'
    stacked parameters over the mesh's ``'pp'`` axis and exposes a callable
    ``(x) -> y`` running the GPipe schedule.  Used for the trunk of a deep
    model; compose embedding/head around it.
    """

    def __init__(self, blocks, mesh, n_microbatches=None, axis="pp",
                 data_axis=None):
        self.blocks = list(blocks)
        self.mesh = mesh
        self.axis = axis
        self.data_axis = data_axis
        self.n_stages = len(self.blocks)
        self.n_microbatches = n_microbatches or 2 * self.n_stages
        self._fn = None
        self._stage_fn = None
        self.stacked = None

    def _build(self, probe_nd):
        import jax
        self._stage_fn, self.stacked = stack_blocks(self.blocks, probe_nd)
        stage_sh = self.mesh.sharded(self.axis)
        self.stacked = {k: jax.device_put(v, stage_sh)
                        for k, v in self.stacked.items()}
        self._fn = gpipe(self._stage_fn, self.n_stages, self.n_microbatches,
                         self.mesh, axis=self.axis, data_axis=self.data_axis)

    def __call__(self, x):
        from . import ndarray as nd
        from .ndarray.ndarray import NDArray
        if not isinstance(x, NDArray):
            x = nd.array(x)
        if self._fn is None:
            probe = NDArray._from_data(x._data[:max(1, x.shape[0] //
                                                    self.n_microbatches)])
            self._build(probe)
        import jax
        act_sh = self.mesh.sharded(self.data_axis) if self.data_axis \
            else self.mesh.replicated()
        xv = jax.device_put(x._data, act_sh)
        return NDArray._from_data(self._fn(self.stacked, xv))
