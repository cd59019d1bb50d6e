"""Ulysses sequence parallelism — all-to-all head sharding
(SURVEY §5.7: ABSENT upstream; the alternative SP design to ring
attention, per DeepSpeed-Ulysses, Jacobs et al. 2023).

The trade: ring attention keeps the sequence sharded throughout and moves
K/V around the ring (n-1 neighbor hops); Ulysses does ONE all-to-all that
re-shards [sequence-parallel → head-parallel], runs completely LOCAL
dense/flash attention per head group, then all-to-alls back.  On TPU both
collectives ride ICI; Ulysses wins when heads ≥ mesh axis size and the
per-device sequence block is short (fewer, larger transfers; attention
itself needs no cross-device math), ring wins for very long sequences
where even L/n × L score tiles blow memory.

 - ``ulysses_attention(q, k, v, axis_name, ...)`` — call INSIDE shard_map
   with q/k/v sequence-sharded (B, H, L/n, D).  Internally:
   all_to_all(seq→heads) → local softmax(QKᵀ)V over the FULL sequence with
   H/n heads → all_to_all(heads→seq).  Fully differentiable (all_to_all
   transposes to the reverse all_to_all).
 - ``ulysses_sequence_parallel_attention(q, k, v, mesh, axis, ...)`` —
   user-facing: takes GLOBAL (B, H, L, D) arrays, shard_maps over the
   mesh axis, returns the global output.  Same signature/semantics as
   ``ring_attention.sequence_parallel_attention`` so layers can switch
   strategies by name.

Causal masking is straightforward here (unlike the ring): after the first
all-to-all every device sees the full sequence, so it's one lower-left
triangular mask on the local (L, L) scores.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

__all__ = ["ulysses_attention", "ulysses_sequence_parallel_attention"]

_NEG_INF = -1e30


def ulysses_attention(q, k, v, axis_name, causal=False, scale=1.0):
    """Inside-shard_map body: q/k/v (B, H, Lb, D) sequence-sharded blocks.

    Same convention as the ring kernel: ``scale`` defaults to 1.0
    (unscaled — the caller applies 1/√d).  The head dim H must divide by
    the axis size n (standard Ulysses requirement — heads are what gets
    scattered)."""
    n = jax.lax.axis_size(axis_name)
    B, H, Lb, D = q.shape
    if H % n:
        raise ValueError(f"ulysses: heads {H} not divisible by axis {n}")

    def seq_to_heads(x):
        # (B, H, Lb, D) seq-sharded → (B, H/n, L, D) head-sharded: the
        # tiled all_to_all splits the head dim into n groups (device i
        # keeps group i) and concatenates the peers' seq blocks, in peer
        # order, along the L dim
        return jax.lax.all_to_all(x, axis_name, split_axis=1,
                                  concat_axis=2, tiled=True)

    def heads_to_seq(x):
        # (B, H/n, L, D) head-sharded → (B, H, Lb, D): exact inverse
        return jax.lax.all_to_all(x, axis_name, split_axis=2,
                                  concat_axis=1, tiled=True)

    qh, kh, vh = seq_to_heads(q), seq_to_heads(k), seq_to_heads(v)
    L = qh.shape[2]
    s = jnp.einsum("bhqd,bhkd->bhqk", qh, kh,
                   preferred_element_type=jnp.float32) * scale
    if causal:
        mask = jnp.tril(jnp.ones((L, L), bool))
        s = jnp.where(mask[None, None], s, _NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhqk,bhkd->bhqd", p, vh.astype(jnp.float32),
                     preferred_element_type=jnp.float32)
    return heads_to_seq(out.astype(q.dtype))


# jit cache: a fresh closure per call would retrace+recompile every step
# (the same trap parallel.py's collective cache exists for)
_jit_cache: dict = {}


def ulysses_sequence_parallel_attention(q, k, v, mesh, axis="sp",
                                        seg_q=None, seg_kv=None,
                                        causal=False, sm_scale=1.0):
    """Global entry: q/k/v (B, H, L, D); shards L over ``axis`` and runs
    the all-to-all schedule.  Drop-in for the ring strategy's
    ``sequence_parallel_attention`` — SAME signature and defaults
    (``sm_scale=1.0`` i.e. unscaled, like the ring kernel: the caller
    applies 1/√d).  Segment masking is a ring-only feature for now."""
    if seg_q is not None or seg_kv is not None:
        raise NotImplementedError(
            "ulysses: segment masking not implemented — use the ring "
            "strategy (sequence_parallel_attention) for segmented batches")
    raw_mesh = mesh.mesh if hasattr(mesh, "mesh") else mesh
    # key by device ids + axes (the _collective_cache convention), not
    # object identity: rebuilding a DeviceMesh per phase must hit the
    # cache, and jax.jit already keys shapes itself
    key = (tuple(d.id for d in raw_mesh.devices.flat),
           tuple(raw_mesh.axis_names), tuple(raw_mesh.devices.shape),
           axis, causal, float(sm_scale))
    f = _jit_cache.get(key)
    if f is None:
        P = jax.sharding.PartitionSpec
        spec = P(None, None, axis, None)

        def body(qq, kk, vv):
            return ulysses_attention(qq, kk, vv, axis, causal=causal,
                                     scale=sm_scale)

        f = jax.jit(jax.shard_map(
            body, mesh=raw_mesh, in_specs=(spec, spec, spec),
            out_specs=spec, check_vma=False))
        _jit_cache[key] = f
    # reshard first: eager callers (TrainStep tape capture) hand over
    # single-device-committed arrays the shard_map would reject; under a
    # jit trace this is just a sharding constraint
    sh = jax.sharding.NamedSharding(
        raw_mesh, jax.sharding.PartitionSpec(None, None, axis, None))
    q, k, v = (jax.device_put(x, sh) for x in (q, k, v))
    return f(q, k, v)
