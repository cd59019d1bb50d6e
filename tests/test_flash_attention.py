"""In-house Pallas flash-attention kernel tests.

Interpreter-mode parity on the CPU platform: ``kernels/flash_attention.py``
forward + custom backward against the dense fp32 oracle
(``ops/contrib.py::_dense_sdpa``), across causal x segment-masking x dtypes
— the same configuration grid the on-chip compile probe walks.  The real-
chip cross-check lives in ``test_tpu_smoke.py`` (flash-vs-dense on the TPU).

Reference role: src/operator/contrib/transformer.cc fused attention ops
(SURVEY §5.7 — the long-context O(L)-memory requirement).
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from mxnet_tpu.kernels.flash_attention import (
    _FIRST, _KIND_SHIFT, _LAST, _MASKED, _SKIPPED, _UNMASKED,
    _bwd_vmem_bytes, _pick_block_h, _tile_schedule, flash_attention)
from mxnet_tpu.ops.contrib import _dense_sdpa


def _inputs(dt, B=2, H=2, L=256, D=64, valid=(200, 256), seed=7):
    r = np.random.RandomState(seed)
    q = jnp.asarray(r.randn(B, H, L, D), dt)
    k = jnp.asarray(r.randn(B, H, L, D), dt)
    v = jnp.asarray(r.randn(B, H, L, D), dt)
    seg = jnp.asarray(
        (np.arange(L)[None, :] < np.asarray(valid)[:, None]).astype(np.int32))
    return q, k, v, seg


def _valid_mask(seg):
    # compare only rows whose query is a real token; pad rows are defined
    # (pad attends pad) but not interesting
    return np.asarray(seg, bool)[:, None, :, None]


@pytest.mark.parametrize("dt,tol", [(jnp.float32, 1e-5),
                                    (jnp.bfloat16, 5e-2)])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_fwd_parity(dt, tol, causal):
    q, k, v, seg = _inputs(dt)
    scale = 1.0 / q.shape[-1] ** 0.5
    out = flash_attention(q, k, v, seg, seg, causal, scale, interpret=True)
    ref = _dense_sdpa(q, k, v, seg, causal, scale)
    assert out.dtype == q.dtype and out.shape == q.shape
    d = np.abs(np.asarray(out, np.float32)
               - np.asarray(ref, np.float32)) * _valid_mask(seg)
    assert d.max() < tol, f"fwd max diff {d.max()}"


@pytest.mark.parametrize("dt,tol", [(jnp.float32, 1e-4),
                                    (jnp.bfloat16, 1e-1)])
@pytest.mark.parametrize("causal", [False, True])
def test_flash_grad_parity(dt, tol, causal):
    q, k, v, seg = _inputs(dt)
    scale = 1.0 / q.shape[-1] ** 0.5
    w = jnp.asarray(_valid_mask(seg), jnp.float32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, seg, seg, causal, scale, interpret=True)
        return jnp.sum(o.astype(jnp.float32) * w * 0.01)

    def loss_dense(q, k, v):
        o = _dense_sdpa(q, k, v, seg, causal, scale)
        return jnp.sum(o.astype(jnp.float32) * w * 0.01)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g1, g2):
        d = float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                  - b.astype(jnp.float32))))
        assert d < tol, f"d{name} max diff {d}"


def test_flash_no_segment_ids():
    """seg=None means full (or pure-causal) attention over every position
    — the STATIC no-mask kernel specialization, fwd AND bwd (the llama
    default path compiles exactly these kernels)."""
    q, k, v, _ = _inputs(jnp.float32)
    scale = 0.125
    ones = jnp.ones(q.shape[:1] + q.shape[2:3], jnp.int32)
    for causal in (True, False):
        out = flash_attention(q, k, v, None, None, causal, scale,
                              interpret=True)
        ref = _dense_sdpa(q, k, v, ones, causal, scale)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-5, atol=1e-5)
        # backward: the no-seg kernel against the dense autodiff
        w = jnp.asarray(np.random.RandomState(4).randn(*q.shape),
                        jnp.float32)

        def lf(q, k, v, _c=causal):
            return jnp.sum(flash_attention(q, k, v, None, None, _c, scale,
                                           interpret=True) * w * 0.01)

        def ld(q, k, v, _c=causal):
            return jnp.sum(_dense_sdpa(q, k, v, ones, _c, scale) * w * 0.01)

        g1 = jax.grad(lf, argnums=(0, 1, 2))(q, k, v)
        g2 = jax.grad(ld, argnums=(0, 1, 2))(q, k, v)
        for name, a, b in zip("qkv", g1, g2):
            d = float(jnp.max(jnp.abs(a - b)))
            assert d < 1e-4, f"no-seg d{name} ({'causal' if causal else 'full'})"


def test_flash_one_sided_segments_rejected():
    """Mixed None/array segment ids raise (equality masking cannot express
    one-sided all-valid — zero-filling silently masked EVERYTHING)."""
    q, k, v, seg = _inputs(jnp.float32, L=128)
    with pytest.raises(ValueError, match="BOTH seg_q and seg_kv"):
        flash_attention(q, k, v, seg, None, False, 0.125, interpret=True)
    with pytest.raises(ValueError, match="BOTH seg_q and seg_kv"):
        flash_attention(q, k, v, None, seg, False, 0.125, interpret=True)


def test_flash_cross_lengths():
    """Lq != Lk (cross-attention shapes): kv segment ids take K's length."""
    r = np.random.RandomState(3)
    B, H, D, Lq, Lk = 2, 2, 64, 128, 256
    q = jnp.asarray(r.randn(B, H, Lq, D), jnp.float32)
    k = jnp.asarray(r.randn(B, H, Lk, D), jnp.float32)
    v = jnp.asarray(r.randn(B, H, Lk, D), jnp.float32)
    seg_q = jnp.ones((B, Lq), jnp.int32)
    seg_kv = jnp.asarray(
        (np.arange(Lk)[None, :] < np.array([180, 256])[:, None])
        .astype(np.int32))
    scale = 1.0 / D ** 0.5
    out = flash_attention(q, k, v, seg_q, seg_kv, False, scale,
                          interpret=True)
    # dense oracle with an explicit rectangular mask
    att = jnp.einsum("bhqd,bhkd->bhqk", q, k) * scale
    mask = seg_q[:, None, :, None] == seg_kv[:, None, None, :]
    att = jnp.where(mask, att, -1e9)
    ref = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(att, -1), v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_flash_fully_masked_rows_finite():
    """Rows whose segment id appears nowhere in kv yield 0 output and 0
    grads — never NaN (the safe_l guard in the kernel's _finish step)."""
    q, k, v, _ = _inputs(jnp.float32, L=128)
    seg_q = jnp.ones((2, 128), jnp.int32)       # queries segment 1
    seg_kv = jnp.zeros((2, 128), jnp.int32)     # keys segment 0 -> no match

    def loss(q, k, v):
        o = flash_attention(q, k, v, seg_q, seg_kv, False, 0.125,
                            interpret=True)
        return jnp.sum(o)

    out = flash_attention(q, k, v, seg_q, seg_kv, False, 0.125,
                          interpret=True)
    assert np.all(np.asarray(out) == 0.0)
    grads = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    for g in grads:
        assert np.all(np.isfinite(np.asarray(g)))
        assert float(jnp.max(jnp.abs(g))) == 0.0


def test_masked_selfatt_flash_eligible_shape():
    """contrib.masked_selfatt at a flash-eligible shape (L=256, D=64)
    matches explicit padding-masked attention math; on this CPU platform
    the platform_dependent picks the dense branch, but the flash gating
    path (probe + eligibility) is exercised end to end."""
    import mxnet_tpu as mx
    from mxnet_tpu.ops import contrib as C
    L, B, H, D = 256, 2, 2, 64
    assert C._flash_eligible(L, D)
    assert not C._flash_eligible(128, D)   # measured floor: dense wins there
    r = np.random.RandomState(5)
    qkv = (r.randn(L, B, 3 * H * D) * 0.3).astype(np.float32)
    vl = np.array([200, 256], np.float32)
    out = mx.nd.contrib.masked_selfatt(mx.nd.array(qkv), mx.nd.array(vl),
                                       heads=H).asnumpy()
    x = qkv.reshape(L, B, H, 3, D)
    q, k, v = (np.transpose(x[:, :, :, i], (1, 2, 0, 3)) for i in range(3))
    seg = (np.arange(L)[None, :] < vl[:, None]).astype(np.int32)
    att = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
    mask = seg[:, None, :, None] == seg[:, None, None, :]
    att = np.where(mask, att, -1e9)
    att = att - att.max(-1, keepdims=True)
    p = np.exp(att)
    p /= p.sum(-1, keepdims=True)
    ref = np.transpose(np.einsum("bhqk,bhkd->bhqd", p, v),
                       (2, 0, 1, 3)).reshape(L, B, H * D)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


def test_masked_att_qkv_gqa_flash_shape():
    """masked_att_qkv with GQA groups at a flash-eligible shape."""
    import mxnet_tpu as mx
    B, Hq, Hkv, L, D = 2, 4, 2, 256, 64
    r = np.random.RandomState(9)
    q = (r.randn(B, Hq, L, D) * 0.3).astype(np.float32)
    k = (r.randn(B, Hkv, L, D) * 0.3).astype(np.float32)
    v = (r.randn(B, Hkv, L, D) * 0.3).astype(np.float32)
    vl = np.array([L, L], np.float32)
    out = mx.nd.contrib.masked_att_qkv(
        mx.nd.array(q), mx.nd.array(k), mx.nd.array(v), mx.nd.array(vl),
        num_kv_groups=Hq // Hkv, causal=True).asnumpy()
    kk = np.repeat(k, Hq // Hkv, axis=1)
    vv = np.repeat(v, Hq // Hkv, axis=1)
    att = np.einsum("bhqd,bhkd->bhqk", q, kk) / np.sqrt(D)
    cm = np.tril(np.ones((L, L), bool))
    att = np.where(cm[None, None], att, -1e9)
    att = att - att.max(-1, keepdims=True)
    p = np.exp(att)
    p /= p.sum(-1, keepdims=True)
    ref = np.einsum("bhqk,bhkd->bhqd", p, vv)
    np.testing.assert_allclose(out, ref, rtol=1e-4, atol=1e-4)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_grad_parity_multi_tile(causal):
    """Explicit small blocks force the STREAMING backward (``flash_bwd``:
    one kernel over a multi-tile grid, dk and dv accumulated down a column,
    dq across a head's columns) — the default-path tests at L<=512 take
    the single-tile fused backward, so this pins the long-seq accumulation
    path."""
    q, k, v, seg = _inputs(jnp.float32)
    scale = 1.0 / q.shape[-1] ** 0.5
    w = jnp.asarray(_valid_mask(seg), jnp.float32)

    def loss_flash(q, k, v):
        o = flash_attention(q, k, v, seg, seg, causal, scale,
                            block_q=128, block_k=128, interpret=True)
        return jnp.sum(o.astype(jnp.float32) * w * 0.01)

    def loss_dense(q, k, v):
        o = _dense_sdpa(q, k, v, seg, causal, scale)
        return jnp.sum(o.astype(jnp.float32) * w * 0.01)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for name, a, b in zip("qkv", g1, g2):
        d = float(jnp.max(jnp.abs(a - b)))
        assert d < 1e-4, f"multi-tile d{name} max diff {d}"


# -- the tile schedule of the streaming kernels --------------------------------

SCHEDULES = [
    # (Lq, Lk, bq, bk, causal)
    pytest.param(4096, 4096, 512, 512, True, id="cell_8x8_causal"),
    pytest.param(512, 512, 128, 128, True, id="4x4_causal"),
    pytest.param(512, 512, 128, 128, False, id="4x4_full"),
    pytest.param(512, 512, 256, 128, True, id="bq_twice_bk_causal"),
    pytest.param(512, 512, 128, 256, True, id="bk_twice_bq_causal"),
    pytest.param(384, 512, 128, 64, True, id="3x8_unequal_blocks_causal"),
    pytest.param(256, 512, 128, 128, True, id="Lk_twice_Lq_causal"),
    pytest.param(512, 256, 128, 128, True, id="Lq_twice_Lk_causal"),
    pytest.param(128, 512, 128, 128, True, id="one_row_causal"),
    pytest.param(256, 512, 128, 128, False, id="cross_lengths_full"),
]


@pytest.mark.parametrize("by_column", [False, True], ids=["rows", "columns"])
@pytest.mark.parametrize("Lq,Lk,bq,bk,causal", SCHEDULES)
def test_tile_schedule_matches_the_dense_mask(Lq, Lk, bq, bk, causal,
                                              by_column):
    """The builder alone, against the dense ``qi >= ki`` mask: a tile's
    class is what the mask reads there (none true / all true / mixed),
    every tile that is not skipped is listed exactly once, a skipped one
    only as the sole tile of a line that has no other, and each row's
    (``by_column``: each column's) first and last flags sit on its first
    and last listed tile."""
    (tq, tk, flags), counts = _tile_schedule(Lq, Lk, bq, bk, causal,
                                             by_column)
    assert tq.dtype == tk.dtype == flags.dtype == np.int32
    dense = (np.arange(Lq)[:, None] >= np.arange(Lk)[None, :]) if causal \
        else np.ones((Lq, Lk), bool)
    n_q, n_kv = Lq // bq, Lk // bk
    want = {}
    for iq in range(n_q):
        for ik in range(n_kv):
            tile = dense[iq * bq:(iq + 1) * bq, ik * bk:(ik + 1) * bk]
            want[iq, ik] = _UNMASKED if tile.all() else \
                _MASKED if tile.any() else _SKIPPED
    assert counts == tuple(list(want.values()).count(c)
                           for c in (_SKIPPED, _UNMASKED, _MASKED))

    listed = list(zip(tq.tolist(), tk.tolist()))
    assert len(set(listed)) == len(listed)
    kinds = {t: f >> _KIND_SHIFT for t, f in zip(listed, flags.tolist())}
    assert all(kinds[t] == want[t] for t in listed)
    assert {t for t, c in want.items() if c != _SKIPPED} <= set(listed)

    # lines in order, the inner index ascending within a line; the flags on
    # the line's two ends and nowhere else
    outer, inner = (tk, tq) if by_column else (tq, tk)
    n_outer = n_kv if by_column else n_q
    assert sorted(set(outer.tolist())) == list(range(n_outer))
    assert np.all(np.diff(outer) >= 0)
    for o in range(n_outer):
        at = np.flatnonzero(outer == o)
        assert np.all(np.diff(inner[at]) > 0)
        assert [bool(f & _FIRST) for f in flags[at]] == \
            [i == 0 for i in range(len(at))]
        assert [bool(f & _LAST) for f in flags[at]] == \
            [i == len(at) - 1 for i in range(len(at))]
        skipped = [t for t in at if flags[t] >> _KIND_SHIFT == _SKIPPED]
        assert not skipped or len(at) == 1
    if causal and not by_column:
        # row 0 under equal blocks: its only tile is the masked one
        first_row = flags[tq == 0]
        if bq == bk:
            assert first_row.tolist() == \
                [_MASKED << _KIND_SHIFT | _LAST | _FIRST]


@pytest.mark.parametrize("H, bq, bk, single_tile, block_h", [
    (12, 512, 512, True, 4),      # bert_base.train_s512 (and _dp4), forward
    (12, 512, 512, "bwd", 3),     # the same cells, the fused backward
    (16, 512, 512, True, 4),      # bert_large.train_s512, forward
    (16, 512, 512, "bwd", 2),     # bert_large.train_s512, backward
    (32, 512, 512, False, 1),     # kanana_2_30b_a3b.train_s4096: streaming,
                                  # one head a grid step (ROADMAP A1)
    (32, 256, 256, False, 4),     # streaming with 256 x 256 blocks
])
def test_pick_block_h_at_the_cells_shapes(H, bq, bk, single_tile, block_h):
    """The kernels' head block at the shapes the benchmark's cells run: no
    option changes it, so a change of these numbers is a change to what a
    cell compiles, and wants a chip reading."""
    assert _pick_block_h(H, bq, bk, single_tile) == block_h


def test_tile_schedule_at_the_cell_shape_counts():
    """Batch 2 x 32 heads x (8 x 8 tiles), causal: 36 of 64 tiles a head
    are visited, 8 of them masked — the numbers PERF.md quotes."""
    (tq, _tk, _f), counts = _tile_schedule(4096, 4096, 512, 512, True)
    assert len(tq) == 36 and counts == (28, 28, 8)
    assert tuple(64 * n for n in counts) == (1792, 1792, 512)


def _dense_oracle(q, k, v, seg_q, seg_kv, causal, scale):
    """Dense attention with a rectangular mask (``_dense_sdpa`` takes one
    segment vector and square scores)."""
    att = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    if seg_q is not None:
        att = jnp.where(seg_q[:, None, :, None] == seg_kv[:, None, None, :],
                        att, -1e9)
    if causal:
        cm = np.arange(q.shape[2])[:, None] >= np.arange(k.shape[2])[None]
        att = jnp.where(cm[None, None], att, -1e9)
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(att, -1), v)


TILE_GRIDS = [
    # Lq, Lk, block_q, block_k, causal, segment ids, block_h (0: picked)
    pytest.param(512, 512, 128, 128, True, False, 0, id="4x4_causal"),
    pytest.param(512, 512, 128, 128, True, True, 0, id="4x4_causal_segids"),
    pytest.param(512, 512, 128, 128, False, True, 0, id="4x4_full_segids"),
    pytest.param(512, 512, 256, 128, True, False, 0,
                 id="2x4_causal_bq_wider"),
    pytest.param(512, 512, 128, 256, True, True, 0,
                 id="4x2_causal_bk_wider_segids"),
    pytest.param(256, 512, 128, 128, False, False, 0, id="2x4_cross_full"),
    pytest.param(256, 512, 64, 128, False, True, 0,
                 id="4x4_cross_full_segids"),
    pytest.param(256, 512, 128, 128, True, False, 0,
                 id="2x4_causal_unreached_keys"),
    # what a dq kept in VMEM across a head's columns can get wrong
    pytest.param(512, 256, 128, 128, True, False, 0,
                 id="4x2_causal_more_queries_than_keys"),
    pytest.param(384, 128, 128, 128, True, False, 0,
                 id="3x1_causal_one_column"),
    pytest.param(512, 512, 128, 128, True, False, 4,
                 id="4x4_causal_four_heads_a_step"),
    pytest.param(256, 512, 128, 128, True, True, 2,
                 id="2x4_causal_unreached_keys_two_heads_a_step_segids"),
    pytest.param(512, 384, 256, 128, False, False, 2,
                 id="2x3_full_two_heads_a_step"),
]


@pytest.mark.parametrize("Lq,Lk,bq,bk,causal,seg,hb", TILE_GRIDS)
def test_flash_streaming_parity_over_tile_grids(Lq, Lk, bq, bk, causal, seg,
                                                hb):
    """Forward and the three gradients of the streaming kernels against the
    dense oracle on grids where skipped, unmasked and masked tiles all
    occur in one call (4 x 4 causal), with and without segment ids, with
    unequal blocks, and with Lq != Lk; key columns no query reaches
    (causal, Lk > Lq) get exact zeros for dk and dv.  The backward keeps
    dq for the whole query length in VMEM from a (b, h)'s first tile to
    its last: every case runs several (b, h) one after the other with
    different data, so a scratch left from the one before would show; the
    last cases add more queries than keys, a single column (every dq row
    gets one contribution), and more than one head a grid step (``hb``: 0
    lets the kernel pick, which is every head of these small shapes when
    the tile is small)."""
    r = np.random.RandomState(13)
    B, H, D = 2, 4 if hb else 2, 32
    q = jnp.asarray(r.randn(B, H, Lq, D), jnp.float32)
    k = jnp.asarray(r.randn(B, H, Lk, D), jnp.float32)
    v = jnp.asarray(r.randn(B, H, Lk, D), jnp.float32)
    seg_q = seg_kv = None
    if seg:
        # three segments a row, their borders inside tiles
        seg_q = jnp.asarray(np.stack([np.arange(Lq) // 200,
                                      np.arange(Lq) // 170]), jnp.int32)
        seg_kv = jnp.asarray(np.stack([np.arange(Lk) // 200,
                                       np.arange(Lk) // 170]), jnp.int32)
    scale = 1.0 / D ** 0.5
    w = jnp.asarray(r.randn(B, H, Lq, D), jnp.float32)

    def flash(q, k, v):
        return flash_attention(q, k, v, seg_q, seg_kv, causal, scale,
                               block_q=bq, block_k=bk, block_h=hb,
                               interpret=True)

    def dense(q, k, v):
        return _dense_oracle(q, k, v, seg_q, seg_kv, causal, scale)

    np.testing.assert_allclose(np.asarray(flash(q, k, v)),
                               np.asarray(dense(q, k, v)),
                               rtol=1e-5, atol=1e-5)
    g1 = jax.grad(lambda *a: jnp.sum(flash(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    g2 = jax.grad(lambda *a: jnp.sum(dense(*a) * w), argnums=(0, 1, 2))(
        q, k, v)
    for name, a, b in zip("qkv", g1, g2):
        d = float(jnp.max(jnp.abs(a - b)))
        assert d < 1e-4, f"d{name} max diff {d}"
    if causal and Lk > Lq:
        assert not np.any(np.asarray(g1[1][:, :, Lq:]))
        assert not np.any(np.asarray(g1[2][:, :, Lq:]))


def test_flash_tiles_counter_counts_a_traced_call():
    """``mxnet_flash_tiles_total{kernel, kind}`` grows by the call's tiles
    over batch and head blocks, where the kernel is built: once for the
    forward and once for the one backward kernel."""
    from mxnet_tpu.telemetry import metrics

    def read(kernel):
        return [getattr(metrics.REGISTRY.get(
            "mxnet_flash_tiles_total",
            labels={"kernel": kernel, "kind": kind}), "value", 0)
            for kind in ("skipped", "unmasked", "masked")]

    names = ("flash_fwd", "flash_bwd")
    gone = ("flash_bwd_dq", "flash_bwd_dkv")
    before = {n: read(n) for n in names}
    q = jnp.zeros((2, 4, 512, 16), jnp.float32)
    jax.grad(lambda q: flash_attention(
        q, q, q, None, None, True, 0.25, block_q=128, block_k=128,
        block_h=2, interpret=True).sum())(q)
    for n in names:   # 2 x (4 / 2) head blocks x (6, 6, 4) tiles
        assert [a - b for a, b in zip(read(n), before[n])] == [24, 24, 16]
    for n in gone:
        assert read(n) == [0, 0, 0]


# -- the VMEM plan of the streaming backward ----------------------------------

MIB = 2 ** 20


@pytest.mark.parametrize("hb,bq,bk,Lq,D,Dv,itemsize,mib", [
    # kanana_2_30b_a3b.train_s4096: one head a step, 192 pads to 256 lanes;
    # double-buffered blocks 2.5 in + 4.75 out (dq 2 x 2), accumulators
    # 4.75 (dq 4), six score tiles 6
    pytest.param(1, 512, 512, 4096, 192, 128, 2, 18.0, id="mla_cell"),
    pytest.param(1, 512, 512, 4096, 192, 128, 4, 24.25, id="mla_cell_f32"),
    # BERT above one tile: 64-wide heads pad to 128 lanes
    pytest.param(1, 512, 512, 2048, 64, 64, 2, 11.0, id="bert_seq2048"),
    pytest.param(1, 512, 512, 8192, 64, 64, 2, 17.0, id="bert_seq8192"),
    pytest.param(4, 256, 256, 4096, 64, 64, 2, 28.0,
                 id="bert_seq4096_blocks256_four_heads"),
    pytest.param(1, 512, 512, 32768, 128, 128, 2, 41.0, id="seq32768_d128"),
])
def test_bwd_vmem_bytes_at_known_shapes(hb, bq, bk, Lq, D, Dv, itemsize,
                                        mib):
    """What the streaming backward asks the compiler for
    (``vmem_limit_bytes``), from the shapes alone; literals, so that a
    change to the plan is a change to what a cell compiles.  The resident
    dq is the term that grows with the query length: 6 bytes a padded lane
    and row in bf16 (two output buffers and the f32 accumulator)."""
    got = _bwd_vmem_bytes(hb, bq, bk, Lq, D, Dv, itemsize)
    assert got == int(mib * MIB), got / MIB
    longer = _bwd_vmem_bytes(hb, bq, bk, 2 * Lq, D, Dv, itemsize)
    lanes = -(-D // 128) * 128
    assert longer - got == hb * Lq * lanes * (2 * itemsize + 4)


def test_bwd_raises_where_dq_cannot_stay_in_vmem():
    """A query length whose gradient does not fit the chip's VMEM raises a
    ValueError that names the length, at trace time (shapes only: nothing
    is allocated); the forward, which keeps no such array, traces."""
    L = 512 * 512
    q = jax.ShapeDtypeStruct((1, 1, L, 128), jnp.bfloat16)

    def out(q, k, v):
        return flash_attention(q, k, v, None, None, True, 0.1)

    assert jax.eval_shape(out, q, q, q).shape == (1, 1, L, 128)
    with pytest.raises(ValueError, match=rf"{L} query rows.*VMEM"):
        jax.eval_shape(jax.grad(lambda *a: out(*a).sum(), argnums=(0, 1, 2)),
                       q, q, q)
    # a quarter of that length is within the plan, and traces
    q = jax.ShapeDtypeStruct((1, 1, 65536, 128), jnp.bfloat16)
    jax.eval_shape(jax.grad(lambda *a: out(*a).astype(jnp.float32).sum(),
                            argnums=(0, 1, 2)), q, q, q)


# -- a value width that differs from the query/key width (latent attention) ---

def _inputs_qk_v(D=24, Dv=16, B=2, H=4, L=256, seed=11):
    r = np.random.RandomState(seed)
    q = jnp.asarray(r.randn(B, H, L, D), jnp.float32)
    k = jnp.asarray(r.randn(B, H, L, D), jnp.float32)
    v = jnp.asarray(r.randn(B, H, L, Dv), jnp.float32)
    return q, k, v


@pytest.mark.parametrize("blocks", [{}, {"block_q": 128, "block_k": 128}],
                         ids=["single_tile", "streaming"])
def test_flash_value_width_differs_causal(blocks):
    """24-wide queries and keys, 16-wide values, causal: out, dO and dV
    take v's width, dQ and dK q's, in the single-tile kernels and in the
    two streaming ones, against the dense oracle."""
    q, k, v = _inputs_qk_v()
    scale = 1.0 / q.shape[-1] ** 0.5
    out = flash_attention(q, k, v, None, None, True, scale, interpret=True,
                          **blocks)
    ref = _dense_sdpa(q, k, v, None, True, scale)
    assert out.shape == (2, 4, 256, 16) == ref.shape
    assert float(jnp.max(jnp.abs(out - ref))) < 1e-5
    w = jnp.asarray(np.random.RandomState(3).randn(*out.shape), jnp.float32)

    def loss_flash(q, k, v):
        return jnp.sum(flash_attention(q, k, v, None, None, True, scale,
                                       interpret=True, **blocks) * w)

    def loss_dense(q, k, v):
        return jnp.sum(_dense_sdpa(q, k, v, None, True, scale) * w)

    g1 = jax.grad(loss_flash, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(loss_dense, argnums=(0, 1, 2))(q, k, v)
    for name, a, b, like in zip("qkv", g1, g2, (q, k, v)):
        assert a.shape == like.shape
        d = float(jnp.max(jnp.abs(a - b)))
        assert d < 1e-4, f"d{name} max diff {d}"


def test_masked_att_qkv_takes_a_narrower_value():
    """The op that the latent-attention block calls: eligible by both
    widths, scaled by the query/key width, v's width out."""
    import mxnet_tpu as mx
    from mxnet_tpu.ops.contrib import _flash_eligible
    assert _flash_eligible(256, 24, 16) and _flash_eligible(4096, 192, 128)
    assert not _flash_eligible(256, 24, 12)
    q, k, v = _inputs_qk_v()
    out = mx.nd.contrib.masked_att_qkv(mx.nd.array(q), mx.nd.array(k),
                                       mx.nd.array(v), None, causal=True)
    ref = _dense_sdpa(q, k, v, None, True, 1.0 / 24 ** 0.5)
    np.testing.assert_allclose(out.asnumpy(), np.asarray(ref), atol=1e-5)


# -- the kernel's residuals under a checkpoint policy --------------------------

@pytest.mark.parametrize("blocks", [{}, {"block_q": 128, "block_k": 128}],
                         ids=["one_tile", "streaming"])
def test_named_residuals_leave_outputs_and_gradients_bit_equal(blocks):
    """``flash_out`` / ``flash_lse`` name the forward rule's residuals; outside
    a checkpoint the names are the identity: out and dq, dk, dv are the
    kernels' own, bit for bit."""
    import importlib
    fa = importlib.import_module("mxnet_tpu.kernels.flash_attention")
    q, k, v, _ = _inputs(jnp.bfloat16)
    scale = 1.0 / q.shape[-1] ** 0.5
    bq, bk = blocks.get("block_q", 512), blocks.get("block_k", 512)
    g = jnp.asarray(np.random.RandomState(5).randn(*q.shape), jnp.bfloat16)
    out, vjp = jax.vjp(lambda q, k, v: flash_attention(
        q, k, v, None, None, True, scale, interpret=True, **blocks), q, k, v)
    want, lse = fa._fwd(q, k, v, None, None, True, scale, bq, bk, 0, True)
    want_grads = fa._bwd(q, k, v, None, None, want, lse, g, True, scale, bq,
                         bk, 0, True)
    np.testing.assert_array_equal(np.asarray(out, np.float32),
                                  np.asarray(want, np.float32))
    for name, a, b in zip("qkv", vjp(g), want_grads):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32), err_msg=name)


def _kernels_in(jaxpr):
    """Names of the Pallas calls in a jaxpr, sub-jaxprs included."""
    names = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            names.append(str(eqn.params["name"]))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names += _kernels_in(sub)
    return names


@pytest.mark.parametrize("blocks", [{}, {"block_q": 128, "block_k": 128}],
                         ids=["one_tile", "streaming"])
def test_under_the_looped_models_policy_the_backward_runs_no_forward_kernel(
        blocks):
    """Under ``jax.checkpoint`` with the policy the looped model's made-again
    layers use, the backward keeps the kernel's out and lse and so runs the
    backward kernel alone; with nothing kept it runs the forward again."""
    from mxnet_tpu.gluon.model_zoo import ouro
    q, k, v, _ = _inputs(jnp.bfloat16)
    g = jnp.ones_like(q)

    def attend(q, k, v):
        return flash_attention(q, k, v, None, None, True, 0.125,
                               interpret=True, **blocks)

    def backward(policy):
        _, vjp = jax.vjp(jax.checkpoint(attend, policy=policy), q, k, v)
        return _kernels_in(jax.make_jaxpr(vjp)(g).jaxpr)

    kept, again = backward(ouro._KEEP), backward(None)
    assert kept and all(n.startswith("flash_bwd") for n in kept), kept
    assert any(n.startswith("flash_fwd") for n in again), again
