"""contrib operators: fused attention, boxes/NMS, misc.

Rebuild of src/operator/contrib/ — most importantly transformer.cc's fused
attention ops (`_contrib_interleaved_matmul_selfatt_qk` etc., the GluonNLP
BERT fast path, SURVEY §5.7) and the detection-model box ops.  The
``contrib.masked_selfatt`` op is the fully-fused TPU path: on TPU it lowers
to the Pallas flash-attention kernel (O(L) memory, MXU-tiled) with
valid_length masking via segment ids; elsewhere it runs the dense masked
softmax(QK^T)V in fp32.  The interleaved layout contracts of the reference
are preserved at every op boundary.
"""

from __future__ import annotations

import functools

from .. import regions
from .registry import register


def _jnp():
    import jax.numpy as jnp
    return jnp


def _attention_region(fn):
    """Run a fused attention op under the region ``attention``: the head
    split, whichever of ``_flash`` and the dense softmax(QK^T)V the shapes
    and the platform pick, and the head merge.  The compiled program and
    the device trace then name attention whatever implements it; the scope
    lies inside the op's own jit, whose transpose keeps it."""
    @functools.wraps(fn)
    def scoped(*args, **kwargs):
        with regions.scope("attention"):
            return fn(*args, **kwargs)
    return scoped


@register("contrib.div_sqrt_dim")
def _div_sqrt_dim(data):
    jnp = _jnp()
    return data / jnp.sqrt(jnp.asarray(data.shape[-1], data.dtype))


# interleaved fused self-attention ops.  Layout contract (reference
# transformer.cc): qkv is (seq, batch, 3*num_heads*head_dim) with q/k/v
# interleaved per head: [q_h0, k_h0, v_h0, q_h1, ...] along the last dim.

def _split_interleaved(qkv, heads):
    jnp = _jnp()
    L, B, E = qkv.shape
    hd = E // (3 * heads)
    x = qkv.reshape(L, B, heads, 3, hd)
    q = x[:, :, :, 0]
    k = x[:, :, :, 1]
    v = x[:, :, :, 2]
    return q, k, v  # (L, B, H, D)


@register("contrib.interleaved_matmul_selfatt_qk")
def _interleaved_matmul_selfatt_qk(qkv, heads=1):
    jnp = _jnp()
    q, k, _ = _split_interleaved(qkv, heads)
    scale = 1.0 / jnp.sqrt(jnp.asarray(q.shape[-1], q.dtype))
    # output (B*H, Lq, Lk) — reference layout
    return jnp.einsum("qbhd,kbhd->bhqk", q * scale, k).reshape(
        -1, qkv.shape[0], qkv.shape[0])


@register("contrib.interleaved_matmul_selfatt_valatt")
def _interleaved_matmul_selfatt_valatt(qkv, att, heads=1):
    jnp = _jnp()
    _, _, v = _split_interleaved(qkv, heads)
    L, B = qkv.shape[0], qkv.shape[1]
    a = att.reshape(B, heads, L, L)
    out = jnp.einsum("bhqk,kbhd->qbhd", a, v)
    return out.reshape(L, B, -1)


# Below this sequence length attention stays dense on a TPU too.  A
# crossover, not structural, last measured on an older toolchain; ROADMAP A5
# is to read both sides of it in the benchmark's cells and then decide here,
# in code.
_FLASH_MIN_SEQ = 256


def _flash_eligible(seq, head_dim, value_dim=None):
    """Whether the Pallas TPU flash kernel's tiling applies to these shapes
    (lane-aligned seq blocks of at least ``_FLASH_MIN_SEQ``; query/key width
    ``head_dim`` and value width ``value_dim``, the same unless given); the
    platform choice itself happens at XLA lowering via
    lax.platform_dependent, never by host-side guessing: a program lowered
    for the CPU (the test platform, or a host-side island of a TPU process)
    carries the dense branch only, one lowered for a TPU — attached or
    merely described — carries the kernel.

    Shapes alone decide: a kernel the TPU compiler refuses raises at
    compile time, it never silently takes the dense path."""
    value_dim = head_dim if value_dim is None else value_dim
    return seq >= _FLASH_MIN_SEQ and seq % 128 == 0 and head_dim % 8 == 0 \
        and value_dim % 8 == 0


def _flash(q, k, v, seg_q, seg_kv, causal, scale):
    """The Pallas flash kernel on (B, H, L, D) — per shard when a
    TrainStep traces over several devices.  GSPMD cannot partition a
    Mosaic kernel (lowering raises), so under a multi-device mesh the
    call is a ``shard_map``: batch over the step's data axes, heads over
    ``tp``, every other axis replicated, and each device runs the kernel
    on its own block.  Sequence stays whole inside the kernel (a sharded
    L is gathered at the boundary; ring/ulysses are the sp paths)."""
    import jax
    from .registry import step_layout
    from ..kernels.flash_attention import flash_attention
    layout = step_layout()
    if layout is None or layout[0].size == 1:
        return flash_attention(q, k, v, seg_q, seg_kv, causal, scale)
    mesh, batch_axes = layout
    n_b = 1
    for a in batch_axes:
        n_b *= mesh.axis_size(a)
    b_spec = batch_axes if batch_axes and q.shape[0] % n_b == 0 else None
    h_spec = "tp" if "tp" in mesh.axis_names \
        and q.shape[1] % mesh.axis_size("tp") == 0 else None
    P = jax.sharding.PartitionSpec
    x_spec, s_spec = P(b_spec, h_spec, None, None), P(b_spec, None)
    segs = () if seg_q is None else (seg_q, seg_kv)

    def local(q, k, v, *segs):
        sq, skv = segs or (None, None)
        return flash_attention(q, k, v, sq, skv, causal, scale)

    return jax.shard_map(
        local, mesh=mesh.mesh,
        in_specs=(x_spec,) * 3 + (s_spec,) * len(segs), out_specs=x_spec,
        check_vma=False)(q, k, v, *segs)


def _dense_sdpa(q, k, v, seg, causal, scale):
    """Masked softmax(QK^T)V, fp32 softmax — the portable fallback and the
    numerics oracle for the flash path (tests compare the two)."""
    import jax
    jnp = _jnp()
    att = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    neg = jnp.asarray(-1e9, jnp.float32)
    if seg is not None:
        mask = seg[:, None, :, None] == seg[:, None, None, :]
        att = jnp.where(mask, att, neg)
    if causal:
        L = att.shape[-1]
        cm = jnp.tril(jnp.ones((L, L), bool))
        att = jnp.where(cm[None, None], att, neg)
    p = jax.nn.softmax(att, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@register("contrib.masked_selfatt")
@_attention_region
def _masked_selfatt(qkv, valid_length=None, heads=1, causal=False):
    """Fused masked multi-head self-attention.

    The single-op TPU replacement for the reference's
    interleaved_matmul_selfatt_qk → (mask) → softmax →
    interleaved_matmul_selfatt_valatt chain (src/operator/contrib/
    transformer.cc; GluonNLP applies the valid_length mask between qk and
    softmax).  Inputs keep the reference interleaved layout contract:
    ``qkv`` is (L, B, 3*heads*head_dim) with per-head [q,k,v] interleaving;
    ``valid_length`` is (B,) — positions >= valid_length[b] neither attend
    nor are attended to.  Returns the attention context (L, B, heads*head_dim).

    On TPU this lowers to the Pallas flash-attention kernel (blockwise
    softmax, O(L) memory — SURVEY §5.7's long-context requirement); the
    masking rides the kernel's segment-id support so padding never
    materializes an (L, L) mask.
    """
    jnp = _jnp()
    L, B, E = qkv.shape
    D = E // (3 * heads)
    q, k, v = _split_interleaved(qkv, heads)       # (L, B, H, D)
    q = jnp.transpose(q, (1, 2, 0, 3))             # (B, H, L, D)
    k = jnp.transpose(k, (1, 2, 0, 3))
    v = jnp.transpose(v, (1, 2, 0, 3))
    out = _attend(q, k, v, valid_length, causal)
    return jnp.transpose(out, (2, 0, 1, 3)).reshape(L, B, heads * D)


def _attend(q, k, v, valid_length, causal):
    """Shared masked-attention core on (B, H, L, D) tensors.

    ``valid_length=None`` means every position is valid — a STATIC fact,
    so the flash kernel compiles its no-mask specialization (no segment
    inputs, no mask/where passes; pure-causal LLM training takes this
    path) and the dense fallback skips the pad mask."""
    jnp = _jnp()
    L, D = q.shape[2], q.shape[3]
    scale = 1.0 / float(D) ** 0.5       # by the query/key width; v's may differ
    if valid_length is None:
        seg = None
    else:
        steps = jnp.arange(L, dtype=jnp.int32)
        seg = (steps[None, :] < valid_length.astype(jnp.int32)[:, None]) \
            .astype(jnp.int32)                      # (B, L): 1=valid, 0=pad
    if _flash_eligible(L, D, v.shape[3]):
        import jax

        if seg is None:
            def _tpu(q, k, v):
                return _flash(q, k, v, None, None, causal, scale)

            def _portable(q, k, v):
                return _dense_sdpa(q, k, v, None, causal, scale)

            return jax.lax.platform_dependent(q, k, v,
                                              tpu=_tpu, default=_portable)

        def _tpu(q, k, v, seg):
            return _flash(q, k, v, seg, seg, causal, scale)

        def _portable(q, k, v, seg):
            return _dense_sdpa(q, k, v, seg, causal, scale)

        # branch resolved per compile platform at lowering time: TPU gets
        # the Pallas kernel, any other platform the dense math
        return jax.lax.platform_dependent(q, k, v, seg,
                                          tpu=_tpu, default=_portable)
    return _dense_sdpa(q, k, v, seg, causal, scale)


@register("contrib.masked_att_qkv")
@_attention_region
def _masked_att_qkv(q, k, v, valid_length=None, num_kv_groups=1,
                    causal=False):
    """Masked attention over SEPARATE q, k (B, H, L, D) and v (B, H, L, Dv)
    tensors (Dv may differ from D: latent attention) — the
    modern-LLM entry point (no interleave round-trip; the BERT-era
    ``masked_selfatt`` keeps the reference transformer.cc layout).

    ``valid_length=None`` = all positions valid, a static fact that lets
    the flash kernel drop every mask pass (the causal-LLM fast path).

    k/v may carry fewer heads (GQA): num_kv_groups = H_q / H_kv query
    groups per kv head; the broadcast happens HERE, adjacent to the
    kernel, so callers never materialize repeated kv projections."""
    jnp = _jnp()
    if num_kv_groups > 1:
        k = jnp.repeat(k, num_kv_groups, axis=1)
        v = jnp.repeat(v, num_kv_groups, axis=1)
    return _attend(q, k, v, valid_length, causal)


@register("contrib.sp_att_qkv", jit=False)
@_attention_region
def _sp_att_qkv(q, k, v, impl="ring", axis="sp", num_kv_groups=1,
                causal=False):
    """Sequence-parallel attention over separate (B, H, L, D) q/k/v —
    the SP counterpart of ``contrib.masked_att_qkv`` (SURVEY §5.7).

    ``impl`` picks the strategy: 'ring' (K/V rotation around the mesh
    axis, O(L/n) score tiles — kernels/ring_attention.py) or 'ulysses'
    (all-to-all head re-sharding, local attention —
    kernels/ulysses.py).  The mesh comes from ``parallel.current_mesh()``
    at call time (registered jit=False so no stale-mesh trace is cached);
    with no active mesh, or the axis absent from it, the op degrades to
    the local fused/dense path so the same model runs single-device.

    Full (unpadded) attention: sequence-parallel training shards L, and
    packing/padding rides segment ids at the kernel level — the Gluon
    entry point here assumes every position valid.
    """
    import jax
    jnp = _jnp()
    from .. import parallel
    if num_kv_groups > 1:
        k = jnp.repeat(k, num_kv_groups, axis=1)
        v = jnp.repeat(v, num_kv_groups, axis=1)
    D = q.shape[3]
    scale = 1.0 / float(D) ** 0.5
    mesh = parallel.current_mesh()
    names = getattr(mesh, "axis_names", ()) if mesh is not None else ()
    if mesh is None or axis not in names:
        return _attend(q, k, v, None, causal)   # static all-valid
    # eager call (e.g. TrainStep's shape-resolve pass): the SP entry
    # points reshard operands across the mesh, so put the result back on
    # the caller's placement or the next eager op sees mixed devices
    eager = not isinstance(q, jax.core.Tracer)
    home = q.sharding if eager else None
    if impl == "ulysses":
        from ..kernels.ulysses import ulysses_sequence_parallel_attention
        out = ulysses_sequence_parallel_attention(
            q, k, v, mesh, axis=axis, causal=causal, sm_scale=scale)
    else:
        from ..kernels.ring_attention import sequence_parallel_attention
        out = sequence_parallel_attention(q, k, v, mesh, axis=axis,
                                          causal=causal, sm_scale=scale)
    return jax.device_put(out, home) if eager else out


# ---------------------------------------------------------------------------
# multihead_attention_* named wrappers (ISSUE 14 satellite): the reference
# registers mha-named variants of the fused attention family alongside the interleaved_matmul ops (SURVEY §2.2
# contrib/ row).  These wrap the SAME cores as the interleaved/masked
# family — `_attend` / `_dense_sdpa` — so there is exactly one attention
# numerics implementation in the tree (the PR-6 no-drift discipline);
# parity against `_dense_sdpa` is pinned by tests/test_contrib_ops.py.
# Layout: SEPARATE (non-interleaved) time-major projections, the shape
# GluonNLP's modular AttentionCell emits — q (Lq, B, heads*D),
# k/v (Lk, B, heads*D).
# ---------------------------------------------------------------------------

def _split_heads(x, heads):
    """(L, B, H*D) -> (B, H, L, D)."""
    jnp = _jnp()
    L, B, E = x.shape
    return jnp.transpose(x.reshape(L, B, heads, E // heads), (1, 2, 0, 3))


def _merge_heads(x):
    """(B, H, L, D) -> (L, B, H*D)."""
    jnp = _jnp()
    B, H, L, D = x.shape
    return jnp.transpose(x, (2, 0, 1, 3)).reshape(L, B, H * D)


@register("contrib.multihead_attention_qk")
def _multihead_attention_qk(q, k, heads=1):
    """Scaled attention scores from separate projections: q (Lq, B,
    heads*D) × k (Lk, B, heads*D) -> (B*heads, Lq, Lk) — the reference
    score layout the interleaved qk ops also emit."""
    jnp = _jnp()
    qh = _split_heads(q, heads)
    kh = _split_heads(k, heads)
    scale = 1.0 / jnp.sqrt(jnp.asarray(qh.shape[-1], q.dtype))
    att = jnp.einsum("bhqd,bhkd->bhqk", qh * scale, kh)
    return att.reshape(-1, q.shape[0], k.shape[0])


@register("contrib.multihead_attention_valatt")
def _multihead_attention_valatt(att, v, heads=1):
    """Apply (B*heads, Lq, Lk) attention weights to v (Lk, B, heads*D)
    -> (Lq, B, heads*D)."""
    jnp = _jnp()
    vh = _split_heads(v, heads)
    B = v.shape[1]
    a = att.reshape(B, heads, att.shape[1], att.shape[2])
    return _merge_heads(jnp.einsum("bhqk,bhkd->bhqd", a, vh))


@register("contrib.multihead_attention")
@_attention_region
def _multihead_attention(q, k, v, valid_length=None, heads=1,
                         causal=False):
    """Fused masked multi-head attention over separate time-major
    projections — the single-op form of the qk → (mask) → softmax →
    valatt chain above, numerically `_dense_sdpa` (fp32 softmax; the
    Pallas flash kernel on TPU via the shared `_attend` core).

    ``valid_length`` (B,) masks KEY positions >= the length — queries
    are always valid (the cross-attention convention; target-side
    padding is the loss's job), and the semantics do NOT depend on
    whether Lq happens to equal Lk.  ``causal`` requires Lq == Lk (a
    causal mask over unequal lengths has no defined alignment here) and
    composes with ``valid_length``."""
    from ..base import MXNetError
    jnp = _jnp()
    if causal and q.shape[0] != k.shape[0]:
        raise MXNetError(
            "contrib.multihead_attention: causal=True needs Lq == Lk "
            f"(got {q.shape[0]} vs {k.shape[0]}) — causal alignment "
            "over unequal lengths is undefined")
    qh = _split_heads(q, heads)
    kh = _split_heads(k, heads)
    vh = _split_heads(v, heads)
    if valid_length is None and q.shape[0] == k.shape[0]:
        # mask-free self-length: the flash-capable core (causal rides
        # the kernel).  Cross lengths stay OFF this path — _attend's
        # flash gate checks only Lq, and an unaligned Lk would hand the
        # Pallas kernel a non-lane-aligned k/v tile.
        out = _attend(qh, kh, vh, None, causal)
    elif valid_length is None:
        scale = 1.0 / float(qh.shape[-1]) ** 0.5
        out = _dense_sdpa_cross(qh, kh, vh, None, scale)
    else:
        # key-side-only masking — _attend's symmetric segment mask
        # would also pad QUERY positions >= valid_length, which is the
        # self-attention contract (masked_selfatt), not this op's
        Lk = k.shape[0]
        steps = jnp.arange(Lk, dtype=jnp.int32)
        seg_kv = (steps[None, :]
                  < valid_length.astype(jnp.int32)[:, None]) \
            .astype(jnp.int32)
        scale = 1.0 / float(qh.shape[-1]) ** 0.5
        out = _dense_sdpa_cross(qh, kh, vh, seg_kv, scale,
                                causal=causal)
    return _merge_heads(out)


@register("contrib.interleaved_matmul_encdec_qk")
def _interleaved_matmul_encdec_qk(q, kv, heads=1):
    jnp = _jnp()
    Lq, B, E = q.shape
    hd = E // heads
    qh = q.reshape(Lq, B, heads, hd)
    Lk = kv.shape[0]
    kvh = kv.reshape(Lk, B, heads, 2, hd)
    k = kvh[:, :, :, 0]
    scale = 1.0 / jnp.sqrt(jnp.asarray(hd, q.dtype))
    return jnp.einsum("qbhd,kbhd->bhqk", qh * scale, k).reshape(-1, Lq, Lk)


@register("contrib.interleaved_matmul_encdec_valatt")
def _interleaved_matmul_encdec_valatt(kv, att, heads=1):
    jnp = _jnp()
    Lk, B, E2 = kv.shape
    hd = E2 // (2 * heads)
    v = kv.reshape(Lk, B, heads, 2, hd)[:, :, :, 1]
    Lq = att.shape[1]
    a = att.reshape(B, heads, Lq, Lk)
    out = jnp.einsum("bhqk,kbhd->qbhd", a, v)
    return out.reshape(Lq, B, -1)


@register("contrib.masked_encdec_att")
@_attention_region
def _masked_encdec_att(q, kv, valid_length=None, heads=1):
    """Fused masked encoder-decoder (cross) attention — the single-op TPU
    replacement for the reference's interleaved_matmul_encdec_qk →
    (mask) → softmax → interleaved_matmul_encdec_valatt chain
    (src/operator/contrib/transformer.cc encdec variants; GluonNLP's
    transformer decoder applies the source valid_length mask between qk
    and softmax).

    Layout contract matches the unfused pair above: ``q`` is (Lq, B,
    heads*D) decoder queries; ``kv`` is (Lk, B, 2*heads*D) with per-head
    [k, v] interleaving from one fused projection of the encoder output;
    ``valid_length`` (B,) masks encoder PADDING keys (queries are always
    valid — target padding is handled by the loss).  Returns (Lq, B,
    heads*D).

    On TPU this lowers to the Pallas flash kernel, which supports
    Lq != Lk (cross-lengths are parity-tested) — padding rides the
    kernel's separate seg_q/seg_kv inputs so no (Lq, Lk) mask tensor is
    ever materialized.
    """
    import jax
    jnp = _jnp()
    Lq, B, E = q.shape
    D = E // heads
    Lk = kv.shape[0]
    qh = jnp.transpose(q.reshape(Lq, B, heads, D), (1, 2, 0, 3))
    kvh = kv.reshape(Lk, B, heads, 2, D)
    kh = jnp.transpose(kvh[:, :, :, 0], (1, 2, 0, 3))    # (B, H, Lk, D)
    vh = jnp.transpose(kvh[:, :, :, 1], (1, 2, 0, 3))
    scale = 1.0 / float(D) ** 0.5
    if valid_length is None:
        seg_q = seg_kv = None
    else:
        steps = jnp.arange(Lk, dtype=jnp.int32)
        seg_kv = (steps[None, :] < valid_length.astype(jnp.int32)[:, None]) \
            .astype(jnp.int32)                            # (B, Lk)
        seg_q = jnp.ones((B, Lq), jnp.int32)              # queries all valid
    if _flash_eligible(Lq, D) and _flash_eligible(Lk, D):
        def _tpu(qh, kh, vh):
            return _flash(qh, kh, vh, seg_q, seg_kv, False, scale)

        def _portable(qh, kh, vh):
            return _dense_sdpa_cross(qh, kh, vh, seg_kv, scale)

        out = jax.lax.platform_dependent(qh, kh, vh,
                                         tpu=_tpu, default=_portable)
    else:
        out = _dense_sdpa_cross(qh, kh, vh, seg_kv, scale)
    return jnp.transpose(out, (2, 0, 1, 3)).reshape(Lq, B, E)


def _dense_sdpa_cross(q, k, v, seg_kv, scale, causal=False):
    """Cross-attention dense fallback: only KEY positions are masked
    (seg_kv (B, Lk); None = all valid), fp32 softmax.  ``causal``
    (callers guarantee Lq == Lk) adds the lower-triangular mask on
    top — the key-only-masked causal path of multihead_attention."""
    import jax
    jnp = _jnp()
    att = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    neg = jnp.asarray(-1e9, jnp.float32)
    if seg_kv is not None:
        att = jnp.where((seg_kv > 0)[:, None, None, :], att, neg)
    if causal:
        cm = jnp.tril(jnp.ones((att.shape[-2], att.shape[-1]), bool))
        att = jnp.where(cm[None, None], att, neg)
    p = jax.nn.softmax(att, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v)


@register("contrib.arange_like", differentiable=False)
def _arange_like(data, start=0.0, step=1.0, repeat=1, axis=None):
    jnp = _jnp()
    if axis is None:
        n = data.size
    else:
        n = data.shape[axis]
    r = start + step * jnp.arange(n, dtype=jnp.float32)
    if repeat != 1:
        r = jnp.repeat(r, repeat)
    return r


@register("contrib.index_array", differentiable=False)
def _index_array(data, axes=None):
    jnp = _jnp()
    import numpy as np
    sh = data.shape
    axes = tuple(axes) if axes is not None else tuple(range(len(sh)))
    grids = jnp.meshgrid(*[jnp.arange(sh[a]) for a in axes], indexing="ij")
    idx = jnp.stack(grids, axis=-1).astype(jnp.int64)
    full = [idx[..., i] for i in range(len(axes))]
    out_sh = tuple(sh[a] for a in axes)
    return jnp.stack(full, axis=-1).reshape(out_sh + (len(axes),))


@register("contrib.gradient_multiplier")
def _gradient_multiplier(data, scalar=1.0):
    import jax

    @jax.custom_vjp
    def f(x):
        return x

    def fwd(x):
        return x, None

    def bwd(_, g):
        return (g * scalar,)

    f.defvjp(fwd, bwd)
    return f(data)


@register("contrib.box_iou", differentiable=False)
def _box_iou(lhs, rhs, format="corner"):
    jnp = _jnp()
    if format == "center":
        def corner(b):
            x, y, w, h = b[..., 0], b[..., 1], b[..., 2], b[..., 3]
            return jnp.stack([x - w / 2, y - h / 2, x + w / 2, y + h / 2], -1)
        lhs, rhs = corner(lhs), corner(rhs)
    l = lhs[..., :, None, :]
    r = rhs[..., None, :, :]
    tl = jnp.maximum(l[..., :2], r[..., :2])
    br = jnp.minimum(l[..., 2:], r[..., 2:])
    wh = jnp.maximum(br - tl, 0)
    inter = wh[..., 0] * wh[..., 1]
    area_l = (l[..., 2] - l[..., 0]) * (l[..., 3] - l[..., 1])
    area_r = (r[..., 2] - r[..., 0]) * (r[..., 3] - r[..., 1])
    return inter / (area_l + area_r - inter + 1e-12)


@register("contrib.box_nms", differentiable=False, jit=False)
def _box_nms(data, overlap_thresh=0.5, valid_thresh=0.0, topk=-1, coord_start=2,
             score_index=1, id_index=-1, background_id=-1, force_suppress=False,
             in_format="corner", out_format="corner"):  # noqa: ARG001
    """Greedy NMS (reference src/operator/contrib/bounding_box.cc).  Runs in
    numpy on host — detection postprocessing is host-side in this rebuild."""
    import numpy as np
    x = np.asarray(data)
    orig_shape = x.shape
    x = x.reshape(-1, x.shape[-2], x.shape[-1])
    out = np.full_like(x, -1.0)
    for b in range(x.shape[0]):
        boxes = x[b]
        scores = boxes[:, score_index]
        valid = scores > valid_thresh
        idx = np.argsort(-scores)
        idx = idx[valid[idx]]
        if topk > 0:
            idx = idx[:topk]
        keep = []
        while len(idx):
            i = idx[0]
            keep.append(i)
            if len(idx) == 1:
                break
            bi = boxes[i, coord_start:coord_start + 4]
            rest = boxes[idx[1:], coord_start:coord_start + 4]
            tl = np.maximum(bi[:2], rest[:, :2])
            br = np.minimum(bi[2:], rest[:, 2:])
            wh = np.maximum(br - tl, 0)
            inter = wh[:, 0] * wh[:, 1]
            a1 = (bi[2] - bi[0]) * (bi[3] - bi[1])
            a2 = (rest[:, 2] - rest[:, 0]) * (rest[:, 3] - rest[:, 1])
            iou = inter / (a1 + a2 - inter + 1e-12)
            same_cls = (boxes[idx[1:], id_index] == boxes[i, id_index]) \
                if (id_index >= 0 and not force_suppress) else np.ones(len(iou), bool)
            idx = idx[1:][~((iou > overlap_thresh) & same_cls)]
        for j, i in enumerate(keep):
            out[b, j] = boxes[i]
    return _jnp().asarray(out.reshape(orig_shape))


@register("contrib.quadratic")
def _quadratic(data, a=0.0, b=0.0, c=0.0):
    """The tutorial op (reference src/operator/contrib/quadratic_op.cc)."""
    return a * data * data + b * data + c


@register("contrib.allclose", differentiable=False)
def _allclose(a, b, rtol=1e-5, atol=1e-8, equal_nan=False):
    jnp = _jnp()
    return jnp.asarray(jnp.allclose(a, b, rtol=rtol, atol=atol,
                                    equal_nan=equal_nan), dtype=jnp.float32)


@register("contrib.hawkes_ll", num_outputs=2)
def _hawkes_ll(lda, alpha, beta, state, lags, marks, valid_length, max_time):
    """Hawkes-process log-likelihood (reference contrib/hawkes_ll.cc)."""
    jnp = _jnp()
    import jax
    K = lda.shape[-1]
    T, N = 0, lags.shape[0]
    mk = jax.nn.one_hot(marks.astype(jnp.int32), K, dtype=lags.dtype)
    steps = jnp.arange(lags.shape[1])
    valid = (steps[None, :] < valid_length[:, None]).astype(lags.dtype)

    def body(carry, xs):
        st, ll = carry
        lag, m, v = xs
        st = st * jnp.exp(-beta * lag[:, None])
        intensity = lda + alpha * st
        lam = jnp.sum(intensity * m, axis=-1)
        ll = ll + v * jnp.log(jnp.maximum(lam, 1e-37))
        st = st + m
        return (st, ll), None

    (st, ll), _ = jax.lax.scan(
        body, (state, jnp.zeros(N, lags.dtype)),
        (lags.T, jnp.transpose(mk, (1, 0, 2)), valid.T))
    compens = jnp.sum(lda * max_time[:, None], axis=-1)
    ll = ll - compens
    return ll, st


# ---------------------------------------------------------------------------
# contrib tail: fft / count_sketch / ctc_loss (reference src/operator/contrib/
# fft.cc, count_sketch.cc and nn/ctc_loss.cc)
# ---------------------------------------------------------------------------

@register("contrib.fft")
def _fft(data, compute_size=128):  # noqa: ARG001 — cuFFT batching knob, n/a
    """reference contrib/fft.cc: FFT along the last dim; output interleaves
    real/imag → last dim doubles (the reference's cuFFT layout contract)."""
    jnp = _jnp()
    f = jnp.fft.fft(data.astype(jnp.float32), axis=-1)
    out = jnp.stack([f.real, f.imag], axis=-1)
    return out.reshape(data.shape[:-1] + (2 * data.shape[-1],)) \
              .astype(jnp.float32)


@register("contrib.ifft")
def _ifft(data, compute_size=128):  # noqa: ARG001
    """reference contrib/fft.cc: inverse of contrib.fft — input interleaved
    real/imag (last dim 2n), output real part (last dim n)."""
    jnp = _jnp()
    n = data.shape[-1] // 2
    x = data.reshape(data.shape[:-1] + (n, 2))
    c = x[..., 0] + 1j * x[..., 1]
    return jnp.fft.ifft(c, axis=-1).real.astype(jnp.float32) * n


@register("contrib.count_sketch")
def _count_sketch(data, h, s, out_dim=16):
    """reference contrib/count_sketch.cc (compact bilinear pooling): project
    (N, d) onto out_dim buckets via hash h (d,) with signs s (d,)."""
    jnp = _jnp()
    idx = h.astype(jnp.int32).reshape(-1)
    sign = s.astype(data.dtype).reshape(-1)
    contrib_vals = data * sign[None, :]
    oh = (idx[:, None] == jnp.arange(out_dim)[None, :]).astype(data.dtype)
    return contrib_vals @ oh


@register("ctc_loss")
def _ctc_loss(data, label, data_lengths=None, label_lengths=None,
              use_data_lengths=False, use_label_lengths=False,
              blank_label="first"):
    """reference nn/ctc_loss.cc (`mx.nd.ctc_loss`): data (T, N, C) time-major
    logits, label (N, L) int classes.  blank_label 'first' → blank id 0 and
    labels are 1-based w.r.t. the alphabet; 'last' → blank id C-1.
    Differentiable (optax forward-backward), so imperative autograd works."""
    import optax
    jnp = _jnp()
    logits = jnp.transpose(data, (1, 0, 2))          # (N, T, C)
    labels = label.astype(jnp.int32)
    N, T, C = logits.shape
    if use_data_lengths and data_lengths is not None:
        steps = jnp.arange(T)
        logit_pad = (steps[None, :]
                     >= data_lengths.astype(jnp.int32)[:, None]) \
            .astype(jnp.float32)
    else:
        logit_pad = jnp.zeros((N, T), jnp.float32)
    L = labels.shape[1]
    if use_label_lengths and label_lengths is not None:
        steps = jnp.arange(L)
        lab_pad = (steps[None, :]
                   >= label_lengths.astype(jnp.int32)[:, None]) \
            .astype(jnp.float32)
    else:
        # reference padding convention: 0 ('first') / -1 pads
        pad_val = 0 if blank_label == "first" else -1
        lab_pad = (labels == pad_val).astype(jnp.float32)
    if blank_label == "last":
        blank_id = C - 1
    else:
        blank_id = 0
    return optax.ctc_loss(logits, logit_pad, labels, lab_pad,
                          blank_id=blank_id)
