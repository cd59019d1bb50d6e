"""Ring attention — sequence/context parallelism over a mesh axis
(SURVEY §5.7: ABSENT upstream; first-class here per the blueprint).

Liu et al., "Ring Attention with Blockwise Transformers" (2023): shard the
sequence over a mesh axis; each device holds its own Q block and rotates
the K/V blocks around the ring (``jax.lax.ppermute`` — ICI
neighbor-to-neighbor traffic) while accumulating blockwise-softmax
partials online, so a sequence of length L costs O(L/n) memory per device
and the K/V transfer overlaps with the block matmuls.

Two layers:

 - ``ring_attention(q, k, v, axis_name, ...)`` — call INSIDE
   ``shard_map`` with q/k/v already sequence-sharded (B, H, L/n, D).
   Pure jnp blockwise math (score tiles are (L/n, L/n) — already the n²
   memory win) with a numerically-stable online combine; fully
   differentiable end to end (ppermute's transpose is the reverse
   rotation, so the backward pass rotates gradients the other way
   automatically — no hand-written ring backward needed).
 - ``sequence_parallel_attention(q, k, v, mesh, axis, ...)`` — takes
   GLOBAL arrays, builds the shard_map over ``mesh``'s ``axis`` and
   returns the globally-assembled output: the user-facing entry for
   gluon attention layers when a sequence-parallel mesh is active.

Causal masking uses the ring step to know each incoming block's global
position: kv block from device j attends fully when j < i, in-block
causally when j == i, not at all when j > i.

Output rows whose every key is masked (fully-padded positions) are
mathematically undefined; like the flash kernels, they return finite
garbage — mask them downstream.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["ring_attention", "sequence_parallel_attention"]

_NEG_INF = -1e30


def _block_attn(q, k, v, scale, mask):
    """One (Lq, Lk) tile → (normalized block output f32, block lse f32).

    Invariant used by the combine: ``out`` is the softmax-weighted value
    over THIS block's keys; ``lse = log sum_k exp(s_k)`` for the block.
    """
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask, s, _NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = jnp.exp(s - m)
    l = jnp.sum(p, axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, v.astype(jnp.float32),
                     preferred_element_type=jnp.float32) / l
    lse = (m + jnp.log(l))[..., 0]
    return out, lse


def _merge(out_a, lse_a, out_b, lse_b):
    """Combine two normalized partials (out, lse) exactly."""
    m = jnp.maximum(lse_a, lse_b)
    ea = jnp.exp(lse_a - m)
    eb = jnp.exp(lse_b - m)
    denom = ea + eb
    out = (out_a * ea[..., None] + out_b * eb[..., None]) / denom[..., None]
    return out, m + jnp.log(denom)


def ring_attention(q, k, v, axis_name, seg_q=None, seg_kv=None,
                   causal=False, sm_scale=1.0):
    """Sequence-parallel attention INSIDE shard_map.

    q, k, v: (B, H, Lb, D) — this device's sequence block; seg_q/seg_kv:
    (B, Lb) int32 segment ids (padding mask; None = attend all).  Returns
    (B, H, Lb, D) in q's dtype.
    """
    n = jax.lax.psum(1, axis_name)
    idx = jax.lax.axis_index(axis_name)
    B, H, Lb, D = q.shape
    if seg_q is None:
        seg_q = jnp.zeros((B, Lb), jnp.int32)
    if seg_kv is None:
        # K's block length, not Q's (they differ if K/V ever carry a
        # different per-device sequence block than Q)
        seg_kv = jnp.zeros((k.shape[0], k.shape[2]), jnp.int32)
    perm = [(i, (i + 1) % n) for i in range(n)]  # rotate kv to the right

    acc = jnp.zeros((B, H, Lb, D), jnp.float32)
    lse = jnp.full((B, H, Lb), _NEG_INF, jnp.float32)
    kb, vb, sb = k, v, seg_kv
    for step in range(n):
        src = (idx - step) % n  # owner of the kv block this step
        seg_mask = seg_q[:, None, :, None] == sb[:, None, None, :]
        if causal:
            qpos = idx * Lb + jax.lax.broadcasted_iota(
                jnp.int32, (Lb, Lb), 0)
            kpos = src * Lb + jax.lax.broadcasted_iota(
                jnp.int32, (Lb, Lb), 1)
            mask = seg_mask & (qpos >= kpos)[None, None]
        else:
            mask = seg_mask
        bout, blse = _block_attn(q, kb, vb, sm_scale, mask)
        acc, lse = _merge(acc, lse, bout, blse)
        if step != n - 1:
            kb = jax.lax.ppermute(kb, axis_name, perm)
            vb = jax.lax.ppermute(vb, axis_name, perm)
            sb = jax.lax.ppermute(sb, axis_name, perm)
    return acc.astype(q.dtype)


def sequence_parallel_attention(q, k, v, mesh, axis="sp", seg_q=None,
                                seg_kv=None, causal=False, sm_scale=1.0):
    """GLOBAL (B, H, L, D) arrays → ring attention over ``mesh[axis]``.

    L must divide evenly over the axis size.  Builds (and caches per call
    site via jit) the shard_map; q/k/v shard on the sequence dim, batch
    and heads stay replicated across the axis (combine with dp/tp axes by
    nesting shard_maps or pjit shardings outside).
    """
    from jax.sharding import PartitionSpec as P

    if hasattr(mesh, "mesh"):            # accept DeviceMesh too
        mesh = mesh.mesh
    n = mesh.shape[axis] if isinstance(mesh.shape, dict) else dict(
        zip(mesh.axis_names, mesh.devices.shape))[axis]
    L = q.shape[2]
    if L % n:
        raise ValueError(f"sequence length {L} must divide over "
                         f"{n} '{axis}' devices")

    spec_x = P(None, None, axis, None)
    spec_s = P(None, axis)
    has_seg = seg_q is not None or seg_kv is not None
    if has_seg:
        # one-sided segment masks are legal: the absent side defaults to
        # the kernel's all-zeros segment (matches kv/q ids of 0) — gating
        # on seg_q alone silently dropped a seg_kv-only padding mask
        if seg_q is None:
            seg_q = jnp.zeros((q.shape[0], q.shape[2]), jnp.int32)
        if seg_kv is None:
            # K's length, not Q's: the sides differ in cross-attention
            seg_kv = jnp.zeros((k.shape[0], k.shape[2]), jnp.int32)

    def local(qb, kb, vb, *segs):
        sq, skv = (segs if has_seg else (None, None))
        return ring_attention(qb, kb, vb, axis, seg_q=sq, seg_kv=skv,
                              causal=causal, sm_scale=sm_scale)

    in_specs = (spec_x, spec_x, spec_x) + ((spec_s, spec_s) if has_seg
                                           else ())
    fn = jax.shard_map(local, mesh=mesh, in_specs=in_specs,
                       out_specs=spec_x, check_vma=False)
    # reshard inputs onto the mesh first: when this runs EAGERLY (e.g. a
    # TrainStep tape-capture pass) the operands arrive committed to a
    # single device and shard_map would reject them; under a jit trace
    # device_put lowers to a sharding constraint instead
    import jax as _jax
    shx = _jax.sharding.NamedSharding(mesh, spec_x)
    shs = _jax.sharding.NamedSharding(mesh, spec_s)
    q, k, v = (_jax.device_put(x, shx) for x in (q, k, v))
    args = (q, k, v) + ((_jax.device_put(seg_q, shs),
                         _jax.device_put(seg_kv, shs)) if has_seg else ())
    return fn(*args)
