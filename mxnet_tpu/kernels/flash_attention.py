"""FlashAttention-2 for TPU in Pallas — forward + full custom backward.

Blockwise-softmax attention with O(L) memory: probabilities never
materialize in HBM (SURVEY §5.7; replaces the reference's full
softmax(QK^T) path in src/operator/contrib/transformer.cc).  Written
in-house rather than wrapping jax.experimental's kernel because this
framework runs with jax_enable_x64 on (MXNet float64 parity) and the
upstream kernel's index arithmetic miscompiles under x64 — everything
here pins explicit int32/float32 types, including BlockSpec index-map
literals (see ``_zi``).  This kernel is the TPU branch of
``contrib.masked_selfatt`` / ``contrib.masked_att_qkv``
(``ops/contrib.py::_attend``), chosen there from shapes and platform
alone: a kernel the compiler refuses raises, nothing falls back to the
dense path.

Layout: q, k are (batch, heads, seq, head_dim) and v is (batch, heads,
seq, value_dim); value_dim may differ from head_dim (latent attention:
192-wide keys, 128-wide values), in which case out, dO and dV take v's
width and dQ, dK take q's.  Segment ids are
(batch, seq) int32 — attention only flows between positions with EQUAL
segment ids (padding mask: valid tokens segment 1, pad tokens 0).

Grid design (canonical TPU flash schedule, head-blocked).  The streaming
kernels (``flash_fwd`` and ``flash_bwd``: more than one tile a sequence)
run a grid of (B, n_h, scheduled tiles): the two sequence dimensions are
ONE grid dimension over the tiles ``_tile_schedule`` lists, made at trace
time from the lengths, the blocks and ``causal``.  A tile no entry of which
passes the causal test is not in the grid at all (no step, no fetch); a
tile every entry of which passes runs the body without the causal mask;
only the tiles the diagonal crosses build the iota mask.  The (q block, kv
block) of each step and its flags ride in three int32 tables handed over as
scalar prefetch, read by the BlockSpec index maps and by the body.  A
non-causal call gets the full rectangle from the same builder (a table
lookup costs it ~0.12 us a step against index maps computed from the grid
indices: measured, PERF.md PR 30).  TPU grid steps run sequentially per
core, so accumulators live in VMEM scratch across steps.  The forward runs
row by row with the kv block innermost: the running (m, l, acc) of a row,
its output block written once on the row's last tile.  The backward is ONE
kernel that runs column by column with the q block innermost and computes
s, p, dp and ds once a tile for all three gradients: dk and dv accumulate
down a kv block's column and write on its last tile, while dq accumulates
in a scratch that holds the WHOLE query length of the head block,
(hb, Lq, D) float32, from the first tile of a (b, h) to its last, where it
is scaled, cast and written to an output block whose index depends on
(b, h) alone.  That scratch and its output block grow with Lq, so the call
reckons its VMEM from the shapes (``_bwd_vmem_bytes``) and asks the
compiler for it; a length that cannot fit raises.  The single-tile kernels
(``flash_fwd_single``, ``flash_bwd_fused``: whole sequence in one block)
keep the plain (B, n_h) grid.
Each step processes a BLOCK OF HEADS (block_h) at once via batched
dot_generals: with head_dim 64 a single-head (bq, 64) x (64, bk) matmul
underfills the MXU and the per-step fixed cost (grid loop + DMA
orchestration) dominates; batching heads divides the sequential step
count by block_h and amortizes that cost (measured ~2.5x over the
single-head schedule at BERT-base shapes).  All matmuls accumulate in
float32 on the MXU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..telemetry import metrics as _metrics

__all__ = ["flash_attention"]

_NEG_INF = -1e30
_M_FLOOR = -1e4  # running-max clamp: keeps exp(s - m) an exact 0.0 for
                 # masked entries (s = -1e30) without a second where pass,
                 # while any real logit above -1e4 is unaffected
_LANES = 128     # VPU lane width: per-row scalars are stored broadcast over lanes
_SUBLANES = 8    # min sublane count — kv segment ids ride a (8, bk) tile
_STAT = 8        # stored width of per-row stats (lse/delta): the kernels
                 # only read [:, :, :1], so a narrow stored broadcast cuts
                 # the (B, H, L, width) HBM read/write 16x vs full lanes
                 # (VMEM pads the lane dim either way)


def _zi():
    """int32 zero for BlockSpec index maps.  Under jax_enable_x64 (this
    framework's default, MXNet float64 parity) a literal ``0`` in an index
    map becomes an i64 constant that Mosaic fails to legalize
    ('func.return (i32, i32, i32, i64)'); an explicit int32 compiles."""
    return jnp.int32(0)


def _pick_block_h(H, bq, bk, single_tile=False):
    """Largest divisor of H whose f32 score tile (Hb, bq, bk) stays under
    the VMEM budget (the tile is the dominant scratch; Mosaic needs
    headroom for double-buffered input blocks).

    STREAMING grids keep the conservative ~1MB budget: the running
    (m, l, acc) scratch lives across kv steps on top of the score tile.
    The SINGLE-TILE kernels (whole seq in one block — no streaming
    scratch) afford more: measured on v5e at BERT-base seq-512 shapes,
    head-batching runs the fused fwd+bwd ~15-25% faster than hb=1 (4.4
    vs 4.9-5.9 ms/layer) by batching more head matmuls per grid step.
    Ceilings are asymmetric: the FWD single-tile kernel holds one
    (hb, bq, bk) f32 score tile (4MB budget → hb=4 at 512x512/12h);
    the fused BWD holds s/p/dp/ds simultaneously — hb=4 there needs
    16.3M scoped vmem against the 16.0M in-context limit (measured OOM
    inside the full train step), so bwd gets 3MB → hb=3."""
    if single_tile == "bwd":
        budget = 3 * 1024 * 1024
    elif single_tile:
        budget = 4 * 1024 * 1024
    else:
        budget = 1024 * 1024
    for hb in range(H, 0, -1):
        if H % hb == 0 and hb * bq * bk * 4 <= budget:
            return hb
    return 1


def _pick_block(L, want):
    """Largest of (want, 256, 128) that divides L — the seq block must
    tile L exactly or the grid silently drops rows."""
    for b in (want, 256, 128):
        if b <= L and L % b == 0:
            return b
    return L


# A tile's class under the causal test, and where it stands in its row:
# what a step of the streaming grids reads from its flags word.
_SKIPPED, _UNMASKED, _MASKED = 0, 1, 2
_KINDS = ("skipped", "unmasked", "masked")
_FIRST, _LAST, _KIND_SHIFT = 1, 2, 2


def _tile_schedule(Lq, Lk, bq, bk, causal, by_column=False):
    """The tiles a streaming kernel visits, in order, as three int32
    tables (q block, kv block, flags) and the number of tiles of each class
    ``(skipped, unmasked, masked)``.  Pure NumPy, made at trace time.

    Class of tile (iq, ik) under ``qi >= ki``: skipped when no entry passes
    (``iq*bq + bq - 1 < ik*bk``), unmasked when every entry does
    (``iq*bq >= ik*bk + bk - 1``), masked when the diagonal crosses it;
    without ``causal`` every tile is unmasked.  Rows run one after the
    other, kv blocks ascending (``by_column``: columns, q blocks
    ascending — the dk/dv accumulation).  ``flags = kind << 2 | last << 1
    | first``: first and last visited tile of the row, where the scratch
    is zeroed and the output block written.  A skipped tile is listed only
    where a row has no other (causal with Lk > Lq: key columns no query
    reaches): it runs neither body, and its first/last flags write the
    zeros that row's output is owed."""
    n_q, n_kv = Lq // bq, Lk // bk
    iq, ik = np.meshgrid(np.arange(n_q), np.arange(n_kv), indexing="ij")
    kind = np.full((n_q, n_kv), _UNMASKED)
    if causal:
        kind[iq * bq < ik * bk + bk - 1] = _MASKED
        kind[iq * bq + bq - 1 < ik * bk] = _SKIPPED
    counts = tuple(int(n) for n in np.bincount(kind.ravel(), minlength=3))
    if by_column:
        iq, ik, kind = iq.T, ik.T, kind.T
    visit = kind != _SKIPPED
    visit[~visit.any(axis=1), 0] = True
    seen = np.cumsum(visit, axis=1)
    first = visit & (seen == 1)
    last = visit & (seen == seen[:, -1:])
    flags = kind << _KIND_SHIFT | last * _LAST | first * _FIRST
    return (iq[visit].astype(np.int32), ik[visit].astype(np.int32),
            flags[visit].astype(np.int32)), counts


def _scheduled(kernel, B, n_h, Lq, Lk, bq, bk, causal, by_column=False):
    """Tables of ``_tile_schedule`` as device constants, with the call's
    tiles banked in ``mxnet_flash_tiles_total`` (the schedule is static, so
    the count is taken where the kernel is built: once a traced call)."""
    tables, counts = _tile_schedule(Lq, Lk, bq, bk, causal, by_column)
    for kind, n in zip(_KINDS, counts):
        _metrics.counter(
            "mxnet_flash_tiles_total",
            "Tiles of the streaming flash kernels' grids by class under "
            "the causal test, over batch and head blocks, a traced call.",
            labels={"kernel": kernel, "kind": kind}).inc(B * n_h * n)
    return [jnp.asarray(t) for t in tables]


def _step_tile(tq_ref, tk_ref, tf_ref):
    """(iq, ik, first, last, kind) of this grid step, from the tables."""
    t = pl.program_id(2)
    flags = tf_ref[t]
    return (tq_ref[t], tk_ref[t], (flags & _FIRST) != 0,
            (flags & _LAST) != 0, flags >> _KIND_SHIFT)


def _run_tile(body, kind, causal):
    """Run ``body(causal)`` as the tile's class asks: with the causal mask
    where the diagonal crosses the tile, without it where every entry
    passes, not at all on a skipped tile.  A non-causal schedule holds
    unmasked tiles only, so its kernel compiles the one body."""
    if not causal:
        body(False)
        return
    pl.when(kind == _MASKED)(functools.partial(body, True))
    pl.when(kind == _UNMASKED)(functools.partial(body, False))


_Q, _KV = 0, 1   # which of the schedule's tables names a step's block


def _rows_spec(hb, blk, width, table):
    """A head block's rows of the step's q block (``table=_Q``) or kv
    block (``_KV``), ``width`` wide, in the streaming grids (b, h, t)."""
    return pl.BlockSpec(
        (1, hb, blk, width),
        lambda b, h, t, *tables: (b, h, tables[table][t], _zi()))


def _mask_block(sq_ref, skv_ref, causal, iq, ik, bq, bk):
    """(bq, bk) bool mask for one tile, or None when the tile needs no
    masking at all (seg_q=None, non-causal — the static no-mask
    specialization: every mask construction + where pass vanishes from
    the compiled kernel).  int32 iota only (x64-safe).

    sq_ref block is (1, bq, LANES) (q ids broadcast over lanes), skv_ref is
    (1, 1, SUBLANES, bk) (kv ids broadcast over sublanes) — the tile-legal
    layout trick for 1-per-row scalars."""
    mask = None
    if sq_ref is not None:
        sq = sq_ref[0][:, :1]      # (bq, 1)
        skv = skv_ref[0, 0][:1, :]  # (1, bk)
        mask = sq == skv
    if causal:
        qi = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0) + iq * bq
        ki = jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1) + ik * bk
        cm = qi >= ki
        mask = cm if mask is None else jnp.logical_and(mask, cm)
    return mask


def _mask_block_T(sqT_ref, skvT_ref, causal, iq, ik, bq, bk):
    """(bk, bq) mask (or None) — the TRANSPOSED tile of the streaming
    backward, built directly from transposed segment layouts (sqT
    (1, 1, SUBLANES, bq) q ids over lanes, skvT (1, bk, LANES) kv ids over
    sublanes) because Mosaic cannot legalize a bool vector transpose
    (`tpu.transpose` on i1)."""
    mask = None
    if sqT_ref is not None:
        sq = sqT_ref[0, 0][:1, :]  # (1, bq)
        skv = skvT_ref[0][:, :1]   # (bk, 1)
        mask = skv == sq           # (bk, bq)
    if causal:
        ki = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 0) + ik * bk
        qi = jax.lax.broadcasted_iota(jnp.int32, (bk, bq), 1) + iq * bq
        cm = qi >= ki
        mask = cm if mask is None else jnp.logical_and(mask, cm)
    return mask


def _seg_row_layout(seg, L):
    """Segment ids per SUBLANE row — (B, L, _LANES), the tile-legal layout
    for q-side ids in (bq, bk) masks.  THE single definition of the
    layout trick; every kernel builder uses these helpers."""
    return jnp.broadcast_to(seg[:, :, None], (seg.shape[0], L, _LANES))


def _seg_lane_layout(seg, L, blk):
    """Segment ids per LANE, one row group per seq block —
    (B, L // blk, _SUBLANES, blk), for kv-side ids in (bq, bk) masks and
    q-side ids in transposed (bk, bq) masks.  The block index is its own
    array dim so a kernel's (1, 1, _SUBLANES, blk) block always spans the
    whole of the last two dims: the TPU lowering only takes a lane dim
    that is a multiple of 128 or the whole dim, and blk may be 64."""
    B = seg.shape[0]
    return jnp.broadcast_to(seg.reshape(B, L // blk, 1, blk),
                            (B, L // blk, _SUBLANES, blk))


def _seg_lane_spec(blk, index_map):
    """BlockSpec for one seq block of a ``_seg_lane_layout`` array;
    ``index_map`` gives (batch, block) from the grid indices."""
    return pl.BlockSpec((1, 1, _SUBLANES, blk),
                        lambda *g: (*index_map(*g), _zi(), _zi()))


def _seg_specs(blk, table):
    """(row layout, lane layout) BlockSpecs of the step's q (``_Q``) or kv
    (``_KV``) block of segment ids, in the streaming grids (b, h, t)."""
    return (pl.BlockSpec((1, blk, _LANES), lambda b, h, t, *tables:
                         (b, tables[table][t], _zi())),
            _seg_lane_spec(blk, lambda b, h, t, *tables:
                           (b, tables[table][t])))


def _apply_mask(s, mask):
    return s if mask is None else \
        jnp.where(mask[None], s, jnp.float32(_NEG_INF))


def _bmm(a, b, contract_a, contract_b):
    """Batched-over-heads MXU matmul: a (Hb, m, ca), b (Hb, n, cb) with the
    given contraction dims, f32 accumulation."""
    return jax.lax.dot_general(
        a, b, (((contract_a,), (contract_b,)), ((0,), (0,))),
        preferred_element_type=jnp.float32,
        precision=jax.lax.Precision.DEFAULT)


# --------------------------------------------------------------------------
# forward
# --------------------------------------------------------------------------

def _fwd_kernel(tq_ref, tk_ref, tf_ref, q_ref, k_ref, v_ref, *rest,
                causal, scale, has_seg):
    if has_seg:
        sq_ref, skv_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    else:
        o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
        sq_ref = skv_ref = None
    iq, ik, first, last, kind = _step_tile(tq_ref, tk_ref, tf_ref)

    @pl.when(first)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, _M_FLOOR)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def _tile(causal):
        # scale is folded into q (a (Hb, bq, d) multiply) instead of into
        # the (Hb, bq, bk) score tile — the kernel is bound on tile-sized
        # elementwise passes, so every saved pass counts
        q = q_ref[0] * jnp.asarray(scale, q_ref.dtype)        # (Hb, bq, d)
        k = k_ref[0]                                          # (Hb, bk, d)
        v = v_ref[0]
        bq, bk = q.shape[1], k.shape[1]

        s = _bmm(q, k, 2, 2)                                  # (Hb, bq, bk)
        # NOTE two predicates were tried for leaving the mask out of a
        # tile.  One computed from segment-id DATA in VMEM (skip the mask
        # when all ids of the tile agree) was measured SLOWER: the branch
        # is known only once the tile is fetched and defeats Mosaic's grid
        # pipelining, so the segment mask only vanishes via the STATIC
        # specialization (seg=None).  The causal one is a SCALAR of the
        # prefetched schedule, known before the step (_run_tile's two
        # bodies), and costs the pipelining nothing — but buys little: at
        # (2, 32, 4096, 192/128) the three kernels of PR 30 take 18.73 ms
        # with it and 18.81 with one masked body on the same tiles (v5e);
        # the mask's iota, compare and select ride passes that the loads
        # and stores of the 1 MB score tile bound anyway
        s = _apply_mask(s, _mask_block(sq_ref, skv_ref, causal, iq, ik,
                                       bq, bk))

        m_prev = m_scr[:, :, :1]                              # (Hb, bq, 1)
        l_prev = l_scr[:, :, :1]
        m_cur = jnp.max(s, axis=2, keepdims=True)             # (Hb, bq, 1)
        # the _M_FLOOR clamp makes exp(s - m_new) an exact 0.0 for masked
        # entries (s = -1e30) — no second where pass; fully-masked rows
        # keep l = 0 and are patched by safe_l in _finish
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)                                # (Hb, bq, bk)
        alpha = jnp.exp(m_prev - m_new)                       # (Hb, bq, 1)
        l_new = l_prev * alpha + jnp.sum(p, axis=2, keepdims=True)
        acc = acc_scr[...] * alpha
        acc_scr[...] = acc + _bmm(p.astype(v.dtype), v, 2, 1)  # (Hb, bq, d)
        m_scr[...] = jnp.broadcast_to(m_new, m_scr.shape)
        l_scr[...] = jnp.broadcast_to(l_new, l_scr.shape)

    _run_tile(_tile, kind, causal)

    @pl.when(last)
    def _finish():
        l = l_scr[:, :, :1]
        safe_l = jnp.where(l == jnp.float32(0.0), jnp.float32(1.0), l)  # fully-masked rows
        o_ref[0] = (acc_scr[...] / safe_l).astype(o_ref.dtype)
        lse = m_scr[:, :, :1] + jnp.log(safe_l)               # (Hb, bq, 1)
        lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _fwd_single_kernel(q_ref, k_ref, v_ref, *rest, causal, scale, has_seg):
    """Single-tile forward (n_q == n_kv == 1): direct softmax, no
    streaming scratch — the running-max/alpha machinery exists only to
    stitch kv blocks together."""
    if has_seg:
        sq_ref, skv_ref, o_ref, lse_ref = rest
    else:
        o_ref, lse_ref = rest
        sq_ref = skv_ref = None
    q = q_ref[0] * jnp.asarray(scale, q_ref.dtype)        # (Hb, bq, d)
    k = k_ref[0]
    v = v_ref[0]
    bq, bk = q.shape[1], k.shape[1]
    s = _bmm(q, k, 2, 2)                                  # (Hb, bq, bk)
    s = _apply_mask(s, _mask_block(sq_ref, skv_ref, causal,
                                   jnp.int32(0), jnp.int32(0), bq, bk))
    m = jnp.maximum(jnp.max(s, axis=2, keepdims=True),
                    jnp.float32(_M_FLOOR))                # (Hb, bq, 1)
    p = jnp.exp(s - m)            # masked: exp(-1e30 - m) == exact 0.0
    l = jnp.sum(p, axis=2, keepdims=True)
    safe_l = jnp.where(l == jnp.float32(0.0), jnp.float32(1.0), l)
    o_ref[0] = (_bmm(p.astype(v.dtype), v, 2, 1) / safe_l) \
        .astype(o_ref.dtype)
    lse = m + jnp.log(safe_l)
    lse_ref[0] = jnp.broadcast_to(lse, lse_ref.shape[1:])


def _fwd_single(q, k, v, seg_q, seg_kv, causal, scale, hb, interpret):
    B, H, Lq, D = q.shape
    Lk, Dv = k.shape[2], v.shape[3]
    n_h = H // hb
    has_seg = seg_q is not None

    def whole(L, d):
        return pl.BlockSpec((1, hb, L, d), lambda b, h: (b, h, _zi(), _zi()))

    in_specs = [whole(Lq, D), whole(Lk, D), whole(Lk, Dv)]
    inputs = [q, k, v]
    if has_seg:
        in_specs += [
            pl.BlockSpec((1, Lq, _LANES), lambda b, h: (b, _zi(), _zi())),
            _seg_lane_spec(Lk, lambda b, h: (b, _zi())),
        ]
        inputs += [
            _seg_row_layout(seg_q, Lq),
            _seg_lane_layout(seg_kv, Lk, Lk),
        ]
    out, lse = pl.pallas_call(
        functools.partial(_fwd_single_kernel, causal=causal, scale=scale,
                          has_seg=has_seg),
        grid=(B, n_h),
        in_specs=in_specs,
        out_specs=[
            whole(Lq, Dv),
            pl.BlockSpec((1, hb, Lq, _STAT),
                         lambda b, h: (b, h, _zi(), _zi())),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Lq, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, Lq, _STAT), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd_single",
    )(*inputs)
    return out, lse[..., 0]


def _fwd(q, k, v, seg_q, seg_kv, causal, scale, block_q, block_k, block_h,
         interpret):
    B, H, Lq, D = q.shape
    Lk, Dv = k.shape[2], v.shape[3]
    bq, bk = _pick_block(Lq, block_q), _pick_block(Lk, block_k)
    single = Lq == bq and Lk == bk
    hb = block_h if block_h else _pick_block_h(H, bq, bk, single)
    if H % hb:
        raise ValueError(f"block_h={hb} must divide num heads {H} "
                         "(a partial head block would silently drop heads)")
    n_q, n_kv, n_h = Lq // bq, Lk // bk, H // hb
    if n_q == 1 and n_kv == 1:
        # whole sequence in one tile: direct-softmax kernel, no streaming
        return _fwd_single(q, k, v, seg_q, seg_kv, causal, scale, hb,
                           interpret)
    has_seg = seg_q is not None
    tables = _scheduled("flash_fwd", B, n_h, Lq, Lk, bq, bk, causal)
    q_rows = functools.partial(_rows_spec, hb, bq, table=_Q)
    kv_rows = functools.partial(_rows_spec, hb, bk, table=_KV)
    in_specs = [q_rows(D), kv_rows(D), kv_rows(Dv)]
    inputs = [q, k, v]
    if has_seg:
        in_specs += [_seg_specs(bq, _Q)[0], _seg_specs(bk, _KV)[1]]
        inputs += [_seg_row_layout(seg_q, Lq),
                   _seg_lane_layout(seg_kv, Lk, bk)]

    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, scale=scale,
                          has_seg=has_seg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=(B, n_h, tables[0].shape[0]),
            in_specs=in_specs,
            out_specs=[q_rows(Dv), q_rows(_STAT)],
            scratch_shapes=[
                pltpu.VMEM((hb, bq, _LANES), jnp.float32),
                pltpu.VMEM((hb, bq, _LANES), jnp.float32),
                pltpu.VMEM((hb, bq, Dv), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, Lq, Dv), q.dtype),
            jax.ShapeDtypeStruct((B, H, Lq, _STAT), jnp.float32),
        ],
        interpret=interpret,
        name="flash_fwd",
    )(*tables, *inputs)
    return out, lse[..., 0]  # lse (B, H, Lq)


# --------------------------------------------------------------------------
# backward
# --------------------------------------------------------------------------

def _bwd_kernel(tq_ref, tk_ref, tf_ref, q_ref, k_ref, v_ref, do_ref, lse_ref,
                delta_ref, *rest, causal, scale, has_seg):
    """Streaming backward: dq, dk and dv from ONE pass over the scheduled
    tiles — s, p, dp and ds once a tile, every block fetched once.  The
    schedule runs column by column: dk, dv accumulate over a kv block's
    column (first and last are that column's) while dq accumulates for the
    whole query length of the head block, in a scratch that stays in VMEM
    from the first tile of a (b, h) to its last."""
    if has_seg:
        sqT_ref, skvT_ref, *rest = rest
    else:
        sqT_ref = skvT_ref = None
    dq_ref, dk_ref, dv_ref, dq_scr, dk_scr, dv_scr = rest
    iq, ik, first, last, kind = _step_tile(tq_ref, tk_ref, tf_ref)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init_rows():
        dq_scr[...] = jnp.zeros_like(dq_scr)

    @pl.when(first)
    def _init_column():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def _tile(causal):
        q = q_ref[0]                                          # (Hb, bq, d)
        qs = q * jnp.asarray(scale, q_ref.dtype)   # scaled copy: sT only —
        # dk below must use RAW q (scaled once, in _finish_column)
        k = k_ref[0]                                          # (Hb, bk, d)
        v = v_ref[0]
        do = do_ref[0]                                        # (Hb, bq, d)
        lse = lse_ref[0][:, :, 0][:, None, :]                 # (Hb, 1, bq)
        delta = delta_ref[0][:, :, 0][:, None, :]             # (Hb, 1, bq)
        bq, bk = q.shape[1], k.shape[1]

        sT = _bmm(k, qs, 2, 2)        # transposed tile: (Hb, bk, bq)
        sT = _apply_mask(sT, _mask_block_T(sqT_ref, skvT_ref, causal,
                                           iq, ik, bq, bk))
        pT = jnp.exp(sT - lse)        # masked entries -> exact 0.0
        dv_scr[...] += _bmm(pT.astype(do.dtype), do, 2, 1)    # (Hb, bk, d)
        dpT = _bmm(v, do, 2, 2)                               # (Hb, bk, bq)
        dsT = (pT * (dpT - delta)).astype(q.dtype)  # scale: in the finishes
        dk_scr[...] += _bmm(dsT, q, 2, 1)                     # (Hb, bk, d)
        # contract over bk (dim 1 of both operands): ds·k without a
        # transpose op, onto the rows of this step's q block
        rows = pl.ds(pl.multiple_of(iq * bq, bq), bq)
        dq_scr[:, rows, :] += _bmm(dsT, k, 1, 1)              # (Hb, bq, d)

    _run_tile(_tile, kind, causal)

    @pl.when(last)
    def _finish_column():
        dk_ref[0] = (dk_scr[...]
                     * jnp.float32(scale)).astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)

    @pl.when(t == pl.num_programs(2) - 1)
    def _finish_rows():
        dq_ref[0] = (dq_scr[...]
                     * jnp.float32(scale)).astype(dq_ref.dtype)


def _bwd_fused_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                      *rest, causal, scale, has_seg):
    """Single-tile fused backward (n_q == n_kv == 1, i.e. seq <= block):
    dq, dk, dv from ONE pass — s and p computed once, dk/dv contract over
    the q dim (no transposes), inputs loaded once.  Above one tile
    ``_bwd_kernel`` does the same over the scheduled tiles."""
    if has_seg:
        sq_ref, skv_ref, dq_ref, dk_ref, dv_ref = rest
    else:
        dq_ref, dk_ref, dv_ref = rest
        sq_ref = skv_ref = None
    q = q_ref[0]                                          # (Hb, bq, d)
    qs = q * jnp.asarray(scale, q_ref.dtype)
    k = k_ref[0]                                          # (Hb, bk, d)
    v = v_ref[0]
    do = do_ref[0]                                        # (Hb, bq, d)
    lse = lse_ref[0][:, :, :1]                            # (Hb, bq, 1)
    delta = delta_ref[0][:, :, :1]                        # (Hb, bq, 1)
    bq, bk = q.shape[1], k.shape[1]

    s = _bmm(qs, k, 2, 2)                                 # (Hb, bq, bk)
    s = _apply_mask(s, _mask_block(sq_ref, skv_ref, causal,
                                   jnp.int32(0), jnp.int32(0), bq, bk))
    p = jnp.exp(s - lse)              # masked entries -> exact 0.0
    dp = _bmm(do.astype(v.dtype), v, 2, 2)                # (Hb, bq, bk)
    ds = p * (dp - delta)
    dq_ref[0] = (_bmm(ds.astype(k.dtype), k, 2, 1)
                 * jnp.float32(scale)).astype(dq_ref.dtype)
    # contract over bq (dim 1 of both operands): the transposed products
    # without any transpose op
    dv_ref[0] = _bmm(p.astype(do.dtype), do, 1, 1).astype(dv_ref.dtype)
    dk_ref[0] = (_bmm(ds.astype(q.dtype), q, 1, 1)
                 * jnp.float32(scale)).astype(dk_ref.dtype)


def _bwd_fused(q, k, v, seg_q, seg_kv, lse_b, delta_b, do, causal, scale,
               hb, interpret):
    """pallas_call wrapper for the single-tile fused backward."""
    B, H, Lq, D = q.shape
    Lk, Dv = k.shape[2], v.shape[3]
    n_h = H // hb
    has_seg = seg_q is not None

    def whole(L, d):
        return pl.BlockSpec((1, hb, L, d), lambda b, h: (b, h, _zi(), _zi()))

    spec_q, spec_k, spec_v = whole(Lq, D), whole(Lk, D), whole(Lk, Dv)
    spec_stat = whole(Lq, _STAT)
    in_specs = [spec_q, spec_k, spec_v, whole(Lq, Dv), spec_stat, spec_stat]
    inputs = [q, k, v, do, lse_b, delta_b]
    if has_seg:
        in_specs += [
            pl.BlockSpec((1, Lq, _LANES), lambda b, h: (b, _zi(), _zi())),
            _seg_lane_spec(Lk, lambda b, h: (b, _zi())),
        ]
        inputs += [
            _seg_row_layout(seg_q, Lq),
            _seg_lane_layout(seg_kv, Lk, Lk),
        ]
    return pl.pallas_call(
        functools.partial(_bwd_fused_kernel, causal=causal, scale=scale,
                          has_seg=has_seg),
        grid=(B, n_h),
        in_specs=in_specs,
        out_specs=[spec_q, spec_k, spec_v],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        interpret=interpret,
        name="flash_bwd_fused",
    )(*inputs)


_VMEM_BYTES = 128 * 2 ** 20          # a TensorCore's VMEM (v4 to v6e)
_VMEM_SCOPED_DEFAULT = 16 * 2 ** 20  # what the compiler gives a kernel unasked


def _bwd_vmem_bytes(hb, bq, bk, Lq, D, Dv, itemsize):
    """VMEM the streaming backward plans for, from its shapes: every input
    and output block twice (the pipeline's double buffer), the three f32
    accumulators — dq's spans the whole query length — and the tile's
    intermediates.  The last dim pads to the lane width."""
    def rows(n, width, size):
        return hb * n * -(-width // _LANES) * _LANES * size

    inputs = (rows(bq, D, itemsize) + rows(bk, D, itemsize)
              + rows(bk, Dv, itemsize) + rows(bq, Dv, itemsize)
              + 2 * rows(bq, _STAT, 4))
    outputs = (rows(Lq, D, itemsize) + rows(bk, D, itemsize)
               + rows(bk, Dv, itemsize))
    scratch = rows(Lq, D, 4) + rows(bk, D, 4) + rows(bk, Dv, 4)
    # sT, pT, dpT, dsT in f32, the bf16 copies the products take and dsT
    # turned for the dq product
    tile = 6 * rows(bk, bq, 4)
    return 2 * (inputs + outputs) + scratch + tile


def _bwd(q, k, v, seg_q, seg_kv, out, lse, do, causal, scale,
         block_q, block_k, block_h, interpret):
    B, H, Lq, D = q.shape
    Lk, Dv = k.shape[2], v.shape[3]
    bq, bk = _pick_block(Lq, block_q), _pick_block(Lk, block_k)
    single = "bwd" if (Lq == bq and Lk == bk) else False
    hb = block_h if block_h else _pick_block_h(H, bq, bk, single)
    if H % hb:
        raise ValueError(f"block_h={hb} must divide num heads {H} "
                         "(a partial head block would silently drop heads)")
    n_q, n_kv, n_h = Lq // bq, Lk // bk, H // hb

    # delta_i = rowsum(dO * O): cheap elementwise reduce, XLA fuses it
    delta = jnp.sum(do.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                   # (B, H, Lq)
    lse_b = jnp.broadcast_to(lse[..., None], lse.shape + (_STAT,))
    delta_b = jnp.broadcast_to(delta[..., None], delta.shape + (_STAT,))
    has_seg = seg_q is not None

    if n_q == 1 and n_kv == 1:
        # whole sequence in one tile: fused dq/dk/dv kernel (one s + one
        # exp + shared loads; see _bwd_fused_kernel)
        return _bwd_fused(q, k, v, seg_q, seg_kv, lse_b, delta_b, do,
                          causal, scale, hb, interpret)

    vmem = _bwd_vmem_bytes(hb, bq, bk, Lq, D, Dv, q.dtype.itemsize)
    if vmem > _VMEM_BYTES:
        raise ValueError(
            f"flash_attention backward: the gradient of {Lq} query rows "
            f"stays in VMEM across a head's tiles, and with the blocks that "
            f"is {vmem / 2**20:.0f} MiB of the chip's "
            f"{_VMEM_BYTES / 2**20:.0f} MiB; split the sequence over chips")

    q_rows = functools.partial(_rows_spec, hb, bq, table=_Q)
    kv_rows = functools.partial(_rows_spec, hb, bk, table=_KV)
    in_specs = [q_rows(D), kv_rows(D), kv_rows(Dv), q_rows(Dv),
                q_rows(_STAT), q_rows(_STAT)]
    inputs = [q, k, v, do, lse_b, delta_b]
    if has_seg:
        # the transposed (bk, bq) mask: q ids over lanes, kv ids over rows
        in_specs += [_seg_specs(bq, _Q)[1], _seg_specs(bk, _KV)[0]]
        inputs += [_seg_lane_layout(seg_q, Lq, bq),
                   _seg_row_layout(seg_kv, Lk)]

    tables = _scheduled("flash_bwd", B, n_h, Lq, Lk, bq, bk, causal,
                        by_column=True)
    return pl.pallas_call(
        functools.partial(_bwd_kernel, causal=causal, scale=scale,
                          has_seg=has_seg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(tables),
            grid=(B, n_h, tables[0].shape[0]),
            in_specs=in_specs,
            out_specs=[
                # all of the head block's query rows: written once, on the
                # last tile of the (b, h)
                pl.BlockSpec((1, hb, Lq, D), lambda b, h, t, *tables:
                             (b, h, _zi(), _zi())),
                kv_rows(D), kv_rows(Dv)],
            scratch_shapes=[
                pltpu.VMEM((hb, Lq, D), jnp.float32),
                pltpu.VMEM((hb, bk, D), jnp.float32),
                pltpu.VMEM((hb, bk, Dv), jnp.float32),
            ]),
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct(k.shape, k.dtype),
            jax.ShapeDtypeStruct(v.shape, v.dtype),
        ],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=max(vmem, _VMEM_SCOPED_DEFAULT)),
        interpret=interpret,
        name="flash_bwd",
    )(*tables, *inputs)


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------

@functools.partial(jax.custom_vjp, nondiff_argnums=(5, 6, 7, 8, 9, 10))
def flash_attention(q, k, v, seg_q=None, seg_kv=None, causal=False,
                    sm_scale=1.0, block_q=512, block_k=512, block_h=0,
                    interpret=False):
    """Blockwise (flash) attention: softmax(scale * Q K^T + mask) V.

    q, k: (B, H, L, D), v: (B, H, L, Dv), Dv == D or not; seg_q/seg_kv:
    (B, L) int32 segment ids (None = no masking); positions attend only
    within equal segment ids.  Returns (B, H, Lq, Dv) in q's dtype.  ``block_h=0`` auto-picks the head-block
    (largest divisor of H under the VMEM budget).  ``interpret=True`` runs
    the Pallas interpreter (CPU tests).

    Numeric contract: the running max is clamped at -1e4 (``_M_FLOOR``) so
    masked logits (-1e30) contribute an exact 0.0 without a second where
    pass.  Consequence: a row whose TRUE max logit is below -1e4 (only
    reachable with exploding/degenerate logits — |scale*q.k| >= 1e4)
    underflows entirely and returns zeros with zero grads instead of exact
    softmax.  Normal-scale inputs (|logits| < 1e4) are unaffected; rows
    that are fully MASKED also return zeros by design.
    """
    out, _ = _flash_fwd(q, k, v, seg_q, seg_kv, causal, sm_scale,
                        block_q, block_k, block_h, interpret)
    return out


def _canon_segs(q, k, seg_q, seg_kv):
    if seg_q is None and seg_kv is None:
        # STATIC no-mask specialization: the kernels compile without seg
        # inputs, mask construction, or where passes (pure causal or
        # full attention)
        return None, None
    if seg_q is None or seg_kv is None:
        # equality masking cannot express "one side all-valid" without
        # knowing the other side's ids; silently zero-filling would make
        # real-id queries match NOTHING (all-masked garbage)
        raise ValueError(
            "flash_attention: pass BOTH seg_q and seg_kv or neither "
            "(one-sided segment ids have no well-defined mask)")
    return seg_q.astype(jnp.int32), seg_kv.astype(jnp.int32)


def _flash_fwd(q, k, v, seg_q, seg_kv, causal, sm_scale, block_q, block_k,
               block_h, interpret):
    sq, skv = _canon_segs(q, k, seg_q, seg_kv)
    out, lse = _fwd(q, k, v, sq, skv, causal, float(sm_scale),
                    block_q, block_k, block_h, interpret)
    return out, (q, k, v, sq, skv, out, lse)


def _flash_fwd_rule(q, k, v, seg_q, seg_kv, causal, sm_scale, block_q,
                    block_k, block_h, interpret):
    out, (q, k, v, sq, skv, _, lse) = _flash_fwd(
        q, k, v, seg_q, seg_kv, causal, sm_scale, block_q, block_k, block_h,
        interpret)
    # named for a jax.checkpoint policy that keeps them (the looped model's
    # made-again layers, gluon/model_zoo/ouro.py); the identity outside one
    out = checkpoint_name(out, "flash_out")
    lse = checkpoint_name(lse, "flash_lse")
    return out, (q, k, v, sq, skv, out, lse)


def _flash_bwd_rule(causal, sm_scale, block_q, block_k, block_h, interpret,
                    res, g):
    q, k, v, sq, skv, out, lse = res
    dq, dk, dv = _bwd(q, k, v, sq, skv, out, lse, g, causal,
                      float(sm_scale), block_q, block_k, block_h, interpret)
    return dq, dk, dv, None, None


flash_attention.defvjp(_flash_fwd_rule, _flash_bwd_rule)
