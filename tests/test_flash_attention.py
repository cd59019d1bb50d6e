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

from mxnet_tpu.kernels.flash_attention import flash_attention
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
        # backward: no-seg dq/dkv kernels against the dense autodiff
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
    """Explicit small blocks force the SPLIT dq/dkv kernels (multi-tile
    grids) — the default-path tests at L<=512 take the single-tile fused
    backward, so this pins the long-seq accumulation path."""
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
    three streaming ones, against the dense oracle."""
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
