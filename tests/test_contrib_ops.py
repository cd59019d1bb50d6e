"""Contrib op tail: fft, count_sketch, ctc_loss, SSD multibox family,
PSROIPooling, DeformableConvolution, gluon.contrib.nn layers.

Reference anchors: src/operator/contrib/{fft,count_sketch,multibox_prior,
multibox_target,multibox_detection,psroi_pooling,deformable_convolution}.cc,
src/operator/nn/ctc_loss.cc, python/mxnet/gluon/contrib/nn/basic_layers.py.
"""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd


def test_fft_ifft_roundtrip_and_values():
    r = np.random.RandomState(0)
    x = r.randn(3, 8).astype(np.float32)
    f = nd.contrib.fft(nd.array(x)).asnumpy()
    ref = np.fft.fft(x, axis=-1)
    np.testing.assert_allclose(f[:, 0::2], ref.real, rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(f[:, 1::2], ref.imag, rtol=1e-4, atol=1e-5)
    # reference ifft is unnormalized (cuFFT): ifft(fft(x)) == n * x
    back = nd.contrib.ifft(nd.array(f)).asnumpy()
    np.testing.assert_allclose(back, 8 * x, rtol=1e-4, atol=1e-4)


def test_count_sketch_projection():
    d = np.array([[1.0, 2.0, 3.0, 4.0]], np.float32)
    h = nd.array(np.array([0, 1, 0, 2], np.float32))
    s = nd.array(np.array([1, -1, 1, 1], np.float32))
    out = nd.contrib.count_sketch(nd.array(d), h, s, out_dim=3).asnumpy()
    np.testing.assert_allclose(out, [[1 + 3, -2, 4]])


def test_ctc_loss_matches_gluon_and_grad():
    T, N, C = 12, 2, 5
    r = np.random.RandomState(1)
    logits = nd.array(r.randn(T, N, C).astype(np.float32))
    label = nd.array(np.array([[1, 2, 0], [3, 1, 2]], np.float32))
    loss = nd.ctc_loss(logits, label)
    assert loss.shape == (N,)
    assert (loss.asnumpy() > 0).all()
    # imperative gradient flows (op registered differentiable via optax)
    logits.attach_grad()
    with autograd.record():
        l = nd.ctc_loss(logits, label).sum()
    l.backward()
    g = logits.grad.asnumpy()
    assert np.abs(g).max() > 0 and np.isfinite(g).all()


def test_ctc_loss_label_lengths_only():
    """Passing ONLY label_lengths must not shift it into the data_lengths
    slot (None positionals are dropped by op wrappers)."""
    import optax
    from mxnet_tpu.gluon import loss as gloss
    r = np.random.RandomState(7)
    T, N, C = 12, 1, 5
    pred = r.randn(N, T, C).astype(np.float32)       # NTC gluon layout
    label = np.array([[1, 2, 2]], np.float32)
    ll = np.array([2], np.float32)                    # only first 2 labels
    out = gloss.CTCLoss(blank_label="first")(
        nd.array(pred), nd.array(label), None, nd.array(ll)).asnumpy()
    ref = optax.ctc_loss(pred, np.zeros((N, T), np.float32),
                         label.astype(np.int32),
                         (np.arange(3)[None] >= ll[:, None])
                         .astype(np.float32), blank_id=0)
    np.testing.assert_allclose(out, np.asarray(ref), rtol=1e-4)


def test_multibox_target_pad_rows_cannot_steal_anchor0():
    """A pad row (cls=-1) must not unassign or claim anchor 0 even when a
    real gt's best anchor IS anchor 0."""
    # anchors: anchor 0 exactly overlaps the gt, others far away
    anchors = nd.array(np.array([[[0.0, 0.0, 0.3, 0.3],
                                  [0.7, 0.7, 0.9, 0.9]]], np.float32))
    label = nd.array(np.array(
        [[[2, 0.0, 0.0, 0.3, 0.3], [-1, 0, 0, 0, 0]]], np.float32))
    loc_t, loc_m, cls_t = nd.contrib.MultiBoxTarget(
        anchors, label, nd.zeros((1, 4, 2)))
    ct = cls_t.asnumpy()[0]
    assert ct[0] == 3.0                    # class 2 → target 3 at anchor 0
    assert ct[1] == 0.0                    # far anchor stays background
    assert np.isfinite(loc_t.asnumpy()).all()
    # the matched anchor's offsets are ~0 (exact overlap), not degenerate
    np.testing.assert_allclose(loc_t.asnumpy()[0][:4], 0.0, atol=1e-5)


def test_multibox_target_hard_negative_mining():
    """negative_mining_ratio=1 with one positive: exactly one hard negative
    (the one the classifier is most confident about) stays background 0,
    other unmatched anchors become ignore_label -1."""
    anchors = nd.array(np.array([[[0.0, 0.0, 0.3, 0.3],
                                  [0.4, 0.4, 0.6, 0.6],
                                  [0.7, 0.7, 0.9, 0.9]]], np.float32))
    label = nd.array(np.array([[[0, 0.0, 0.0, 0.3, 0.3]]], np.float32))
    cls_pred = np.zeros((1, 3, 3), np.float32)
    cls_pred[0, 1, 2] = 0.9         # anchor 2 = most object-confident
    cls_pred[0, 1, 1] = 0.2
    _, _, cls_t = nd.contrib.MultiBoxTarget(
        anchors, label, nd.array(cls_pred), negative_mining_ratio=1.0,
        negative_mining_thresh=0.5)
    ct = cls_t.asnumpy()[0]
    assert ct[0] == 1.0             # the positive
    assert ct[2] == 0.0             # hardest negative kept as background
    assert ct[1] == -1.0            # remaining negative ignored


def test_multibox_detection_emits_secondary_classes():
    """An anchor confident for two classes yields candidates for both
    (reference emits one candidate per non-background class, not argmax)."""
    anchors = nd.array(np.array([[[0.1, 0.1, 0.5, 0.5]]], np.float32))
    cls_prob = np.zeros((1, 3, 1), np.float32)
    cls_prob[0, 1, 0] = 0.45                # class 0
    cls_prob[0, 2, 0] = 0.44                # class 1
    det = nd.contrib.MultiBoxDetection(
        nd.array(cls_prob), nd.zeros((1, 4)), anchors).asnumpy()[0]
    kept = det[det[:, 0] >= 0]
    assert len(kept) == 1                   # same-anchor: capped by A rows
    # without force_suppress, different classes don't suppress each other —
    # but output is capped at A rows; widen A to see both
    anchors2 = nd.array(np.array([[[0.1, 0.1, 0.5, 0.5],
                                   [0.6, 0.6, 0.9, 0.9]]], np.float32))
    cls_prob2 = np.zeros((1, 3, 2), np.float32)
    cls_prob2[0, 1, 0] = 0.45
    cls_prob2[0, 2, 0] = 0.44
    det2 = nd.contrib.MultiBoxDetection(
        nd.array(cls_prob2), nd.zeros((1, 8)), anchors2).asnumpy()[0]
    kept2 = det2[det2[:, 0] >= 0]
    assert sorted(kept2[:, 0].tolist()) == [0.0, 1.0]


def test_multibox_prior_layout():
    feat = nd.zeros((1, 8, 4, 5))
    anchors = nd.contrib.MultiBoxPrior(feat, sizes=(0.4, 0.2),
                                       ratios=(1, 2, 0.5))
    # A = sizes + ratios - 1 = 4 per pixel
    assert anchors.shape == (1, 4 * 5 * 4, 4)
    a = anchors.asnumpy()[0].reshape(4, 5, 4, 4)
    # first anchor at pixel (0,0): size .4, ratio 1, centered (0.5/5, 0.5/4)
    cx, cy = 0.5 / 5, 0.5 / 4
    np.testing.assert_allclose(a[0, 0, 0],
                               [cx - 0.2, cy - 0.2, cx + 0.2, cy + 0.2],
                               atol=1e-6)


def test_multibox_target_matching():
    feat = nd.zeros((1, 4, 4, 4))
    anchors = nd.contrib.MultiBoxPrior(feat, sizes=(0.3,), ratios=(1,))
    A = anchors.shape[1]
    # one gt box matching the anchor near (0.375, 0.375)
    label = nd.array(np.array(
        [[[1, 0.25, 0.25, 0.5, 0.5], [-1, 0, 0, 0, 0]]], np.float32))
    loc_t, loc_m, cls_t = nd.contrib.MultiBoxTarget(
        anchors, label, nd.zeros((1, 3, A)))
    ct = cls_t.asnumpy()[0]
    assert (ct > 0).sum() >= 1              # at least the forced best anchor
    assert set(np.unique(ct)) <= {0.0, 2.0}  # class id 1 → target 2 (1+cls)
    lm = loc_m.asnumpy()[0].reshape(A, 4)
    assert ((lm.sum(1) > 0) == (ct > 0)).all()  # mask aligns with matches


def test_multibox_detection_decodes_and_nms():
    feat = nd.zeros((1, 4, 2, 2))
    # two sizes per pixel → same-center boxes with IoU 0.69: NMS fodder
    anchors = nd.contrib.MultiBoxPrior(feat, sizes=(0.5, 0.6), ratios=(1,))
    A = anchors.shape[1]
    cls_prob = np.zeros((1, 2, A), np.float32)
    cls_prob[0, 0] = 0.1
    cls_prob[0, 1] = 0.9                     # all anchors confident class 0
    det = nd.contrib.MultiBoxDetection(
        nd.array(cls_prob), nd.zeros((1, A * 4)), anchors,
        nms_threshold=0.3).asnumpy()[0]
    kept = det[det[:, 0] >= 0]
    assert len(kept) == A // 2               # one survivor per pixel
    np.testing.assert_allclose(kept[0, 1], 0.9, atol=1e-6)


def test_psroi_pooling_position_sensitivity():
    """Each output bin must read its own channel group: constant-per-channel
    input makes output bin (d, ph, pw) equal the value of its group chan."""
    D, g = 2, 2
    C = D * g * g
    data = np.zeros((1, C, 8, 8), np.float32)
    for c in range(C):
        data[0, c] = c
    rois = nd.array(np.array([[0, 0, 0, 8, 8]], np.float32))
    out = nd.contrib.PSROIPooling(nd.array(data), rois, output_dim=D,
                                  pooled_size=g, group_size=g).asnumpy()
    for d in range(D):
        for py in range(g):
            for px in range(g):
                expect = (d * g + py) * g + px
                np.testing.assert_allclose(out[0, d, py, px], expect)


def test_deformable_conv_zero_offset_equals_conv():
    r = np.random.RandomState(2)
    x = nd.array(r.randn(2, 3, 10, 10).astype(np.float32))
    w = nd.array(r.randn(5, 3, 3, 3).astype(np.float32))
    off = nd.zeros((2, 18, 8, 8))
    out = nd.contrib.DeformableConvolution(x, off, w, kernel=(3, 3),
                                           num_filter=5)
    ref = nd.Convolution(x, w, kernel=(3, 3), num_filter=5, no_bias=True)
    np.testing.assert_allclose(out.asnumpy(), ref.asnumpy(),
                               rtol=1e-4, atol=1e-4)


def test_deformable_conv_integer_shift():
    """Constant integer offset == sampling the shifted image."""
    r = np.random.RandomState(3)
    x_np = r.randn(1, 2, 9, 9).astype(np.float32)
    w = nd.array(r.randn(3, 2, 3, 3).astype(np.float32))
    off_np = np.zeros((1, 18, 7, 7), np.float32)
    off_np[:, 0::2] = 1.0                    # shift all taps down 1 px
    out = nd.contrib.DeformableConvolution(
        nd.array(x_np), nd.array(off_np), w, kernel=(3, 3), num_filter=3)
    shifted = np.pad(x_np, ((0, 0), (0, 0), (0, 1), (0, 0)))[:, :, 1:, :]
    ref = nd.Convolution(nd.array(shifted), w, kernel=(3, 3), num_filter=3,
                         no_bias=True)
    np.testing.assert_allclose(out.asnumpy(), ref.asnumpy(),
                               rtol=1e-4, atol=1e-4)


def test_proposal_rpn():
    """RPN proposals: right shape, batch indices, image clipping, and NMS
    keeping the highest-objectness box first."""
    r = np.random.RandomState(0)
    N, A, H, W = 2, 9, 6, 6
    kw = dict(scales=(8, 16, 32), ratios=(0.5, 1, 2))
    cls = nd.array(r.rand(N, 2 * A, H, W).astype(np.float32))
    bbox = nd.array((r.randn(N, 4 * A, H, W) * 0.1).astype(np.float32))
    info = nd.array(np.array([[96, 96, 1.0]] * N, np.float32))
    rois = nd.contrib.Proposal(cls, bbox, info, rpn_pre_nms_top_n=100,
                               rpn_post_nms_top_n=20, rpn_min_size=4,
                               **kw).asnumpy()
    assert rois.shape == (N * 20, 5)
    assert (rois[:20, 0] == 0).all() and (rois[20:, 0] == 1).all()
    assert (rois[:, 1:] >= 0).all() and (rois[:, 1:] <= 95).all()
    rois2, sc = nd.contrib.Proposal(cls, bbox, info, rpn_post_nms_top_n=10,
                                    output_score=True, **kw)
    sc = sc.asnumpy()
    # first kept roi per image carries the max objectness of its image
    fg = cls.asnumpy()[:, A:]
    assert sc[0, 0] >= fg[0].max() - 1e-4 or sc[0, 0] > 0.99
    # MultiProposal is the batch alias
    mr = nd.contrib.MultiProposal(cls, bbox, info, rpn_post_nms_top_n=20,
                                  **kw).asnumpy()
    assert mr.shape == (N * 20, 5)


def test_sync_batch_norm_and_contrib_layers():
    from mxnet_tpu.gluon.contrib import nn as cnn
    from mxnet_tpu.gluon import nn
    sbn = cnn.SyncBatchNorm(in_channels=4, num_devices=8)
    sbn.initialize()
    x = nd.array(np.random.RandomState(4).randn(2, 4, 3, 3)
                 .astype(np.float32))
    with autograd.record():
        y = sbn(x)
    # training-mode BN: per-channel batch stats normalize to ~0 mean
    m = y.asnumpy().mean(axis=(0, 2, 3))
    np.testing.assert_allclose(m, 0, atol=1e-5)

    ident = cnn.Identity()
    np.testing.assert_array_equal(ident(x).asnumpy(), x.asnumpy())

    conc = cnn.Concurrent(axis=1)
    conc.add(cnn.Identity())
    conc.add(cnn.Identity())
    assert conc(x).shape == (2, 8, 3, 3)


def test_deformable_conv_numeric_gradient():
    """Finite-difference check through the bilinear-gather deformable conv
    (test_utils.check_numeric_gradient, the reference's universal grad
    oracle)."""
    r = np.random.RandomState(5)
    x = nd.array(r.randn(1, 2, 6, 6).astype(np.float32))
    w = nd.array(r.randn(2, 2, 3, 3).astype(np.float32) * 0.5)
    off = nd.array((r.randn(1, 18, 4, 4) * 0.3).astype(np.float32))
    x.attach_grad(); w.attach_grad(); off.attach_grad()
    with autograd.record():
        out = nd.contrib.DeformableConvolution(x, off, w, kernel=(3, 3),
                                               num_filter=2)
        loss = (out * out).sum()
    loss.backward()
    eps = 1e-2
    xn = x.asnumpy()
    for (i, j) in [(0, 0), (1, 3)]:
        pert = xn.copy(); pert[0, 0, i, j] += eps
        lp = float((nd.contrib.DeformableConvolution(
            nd.array(pert), off, w, kernel=(3, 3), num_filter=2) ** 2)
            .sum().asnumpy())
        pert[0, 0, i, j] -= 2 * eps
        lm = float((nd.contrib.DeformableConvolution(
            nd.array(pert), off, w, kernel=(3, 3), num_filter=2) ** 2)
            .sum().asnumpy())
        fd = (lp - lm) / (2 * eps)
        np.testing.assert_allclose(x.grad.asnumpy()[0, 0, i, j], fd,
                                   rtol=0.05, atol=0.05)


def test_psroi_pooling_gradient_flows():
    data = nd.array(np.random.RandomState(6)
                    .randn(1, 8, 6, 6).astype(np.float32))
    rois = nd.array(np.array([[0, 0, 0, 5, 5]], np.float32))
    data.attach_grad()
    with autograd.record():
        out = nd.contrib.PSROIPooling(data, rois, output_dim=2,
                                      pooled_size=2, group_size=2)
        loss = out.sum()
    loss.backward()
    g = data.grad.asnumpy()
    assert np.isfinite(g).all() and np.abs(g).sum() > 0


def test_fft_gradient_roundtrip():
    x = nd.array(np.random.RandomState(7).randn(2, 8).astype(np.float32))
    x.attach_grad()
    with autograd.record():
        loss = (nd.contrib.ifft(nd.contrib.fft(x)) / 8).sum()
    loss.backward()
    # d/dx sum(ifft(fft(x))/n) == 1 elementwise (linear roundtrip)
    np.testing.assert_allclose(x.grad.asnumpy(), 1.0, rtol=1e-4, atol=1e-4)


def test_masked_encdec_att_matches_unfused_chain():
    """The fused cross-attention op (r5) ≡ the reference-shaped unfused
    chain interleaved_matmul_encdec_qk → (mask) → softmax →
    interleaved_matmul_encdec_valatt — the layout contract both share."""
    r = np.random.RandomState(7)
    Lq, Lk, B, H, D = 6, 9, 2, 2, 4
    q = nd.array(r.randn(Lq, B, H * D).astype(np.float32))
    kv = nd.array(r.randn(Lk, B, 2 * H * D).astype(np.float32))
    vl = nd.array(np.array([9, 5], np.float32))

    fused = nd.contrib.masked_encdec_att(q, kv, vl, heads=H).asnumpy()

    att = nd.contrib.interleaved_matmul_encdec_qk(q, kv, heads=H)
    # source-padding mask between qk and softmax (GluonNLP decoder contract)
    a = att.asnumpy().reshape(B, H, Lq, Lk)
    mask = np.arange(Lk)[None, :] < vl.asnumpy()[:, None]
    a = np.where(mask[:, None, None, :], a, -1e9)
    p = np.exp(a - a.max(-1, keepdims=True))
    p = (p / p.sum(-1, keepdims=True)).reshape(B * H, Lq, Lk)
    chain = nd.contrib.interleaved_matmul_encdec_valatt(
        kv, nd.array(p.astype(np.float32)), heads=H).asnumpy()
    np.testing.assert_allclose(fused, chain, rtol=1e-4, atol=1e-5)


def test_masked_encdec_att_grads_flow():
    from mxnet_tpu import autograd
    r = np.random.RandomState(8)
    q = nd.array(r.randn(4, 2, 8).astype(np.float32))
    kv = nd.array(r.randn(5, 2, 16).astype(np.float32))
    q.attach_grad()
    kv.attach_grad()
    with autograd.record():
        out = nd.contrib.masked_encdec_att(q, kv, None, heads=2)
        loss = (out * out).sum()
    loss.backward()
    assert np.isfinite(q.grad.asnumpy()).all()
    assert np.abs(kv.grad.asnumpy()).sum() > 0


# ---------------------------------------------------------------------------
# multihead_attention_* named wrappers (ISSUE 14 satellite):
# parity against ops.contrib._dense_sdpa, the tree's ONE
# attention-numerics oracle.
# ---------------------------------------------------------------------------

def _mha_ref(q, k, v, H, valid_length=None, causal=False):
    """Key-only-masked oracle on (L, B, H*D) inputs: _dense_sdpa for the
    mask-free cases (the shared numerics core) and an explicit
    keys-masked softmax otherwise — queries are ALWAYS valid, the op's
    documented contract (independent of Lq == Lk)."""
    import jax.numpy as jnp
    from mxnet_tpu.ops.contrib import _dense_sdpa

    def heads(x):
        L, B, E = x.shape
        return jnp.transpose(
            jnp.asarray(x).reshape(L, B, H, E // H), (1, 2, 0, 3))

    D = q.shape[-1] // H
    Lq, B = q.shape[0], q.shape[1]
    if valid_length is None:
        out = np.asarray(_dense_sdpa(heads(q), heads(k), heads(v), None,
                                     causal, 1.0 / float(D) ** 0.5))
        return out.transpose(2, 0, 1, 3).reshape(Lq, B, -1)
    Lk = k.shape[0]
    att = np.einsum("qbhd,kbhd->bhqk",
                    q.reshape(Lq, B, H, D) / np.sqrt(D),
                    k.reshape(Lk, B, H, D))
    att = np.where((np.arange(Lk)[None, :] < valid_length[:, None])
                   [:, None, None, :], att, -1e9)
    if causal:
        att = np.where(np.tril(np.ones((Lq, Lk), bool))[None, None],
                       att, -1e9)
    p = np.exp(att - att.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    return np.einsum("bhqk,kbhd->qbhd", p,
                     v.reshape(Lk, B, H, D)).reshape(Lq, B, H * D)


def test_multihead_attention_matches_dense_sdpa():
    r = np.random.RandomState(11)
    L, B, H, D = 6, 3, 2, 4
    q = r.randn(L, B, H * D).astype(np.float32)
    k = r.randn(L, B, H * D).astype(np.float32)
    v = r.randn(L, B, H * D).astype(np.float32)
    for vl, causal in ((None, False), (np.array([6, 3, 5]), False),
                      (None, True), (np.array([4, 6, 2]), True)):
        got = nd.contrib.multihead_attention(
            nd.array(q), nd.array(k), nd.array(v),
            None if vl is None else nd.array(vl.astype(np.float32)),
            heads=H, causal=causal).asnumpy()
        want = _mha_ref(q, k, v, H, valid_length=vl, causal=causal)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6,
                                   err_msg=f"vl={vl} causal={causal}")


def test_multihead_attention_cross_lengths():
    """Lq != Lk takes the cross path: key-side masking only."""
    r = np.random.RandomState(12)
    Lq, Lk, B, H, D = 5, 9, 2, 2, 4
    q = r.randn(Lq, B, H * D).astype(np.float32)
    k = r.randn(Lk, B, H * D).astype(np.float32)
    v = r.randn(Lk, B, H * D).astype(np.float32)
    vl = np.array([9, 4])
    got = nd.contrib.multihead_attention(
        nd.array(q), nd.array(k), nd.array(v),
        nd.array(vl.astype(np.float32)), heads=H).asnumpy()
    # oracle: _dense_sdpa_cross == _dense_sdpa with key-side-only seg;
    # build it by masking scores directly
    att = np.einsum("qbhd,kbhd->bhqk",
                    q.reshape(Lq, B, H, D) / np.sqrt(D),
                    k.reshape(Lk, B, H, D))
    att = np.where((np.arange(Lk)[None, :] < vl[:, None])
                   [:, None, None, :], att, -1e9)
    p = np.exp(att - att.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    want = np.einsum("bhqk,kbhd->qbhd", p,
                     v.reshape(Lk, B, H, D)).reshape(Lq, B, H * D)
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_multihead_attention_mask_independent_of_length_coincidence():
    """Key-only masking must NOT flip to the self-attention two-sided
    mask just because Lq happens to equal Lk (review regression): the
    first Lq query rows of an (Lq, Lk+1)-shaped cross call — key row
    Lk padded away by valid_length — must equal the (Lq, Lq)-shaped
    call on the same keys."""
    r = np.random.RandomState(15)
    L, B, H, D = 6, 2, 2, 4
    q = r.randn(L, B, H * D).astype(np.float32)
    k = r.randn(L + 1, B, H * D).astype(np.float32)
    v = r.randn(L + 1, B, H * D).astype(np.float32)
    vl = np.array([3.0, 5.0], np.float32)
    eq = nd.contrib.multihead_attention(
        nd.array(q), nd.array(k[:L]), nd.array(v[:L]), nd.array(vl),
        heads=H).asnumpy()
    cross = nd.contrib.multihead_attention(
        nd.array(q), nd.array(k), nd.array(v), nd.array(vl),
        heads=H).asnumpy()
    np.testing.assert_allclose(eq, cross, rtol=1e-5, atol=1e-6)


def test_multihead_attention_causal_cross_raises():
    r = np.random.RandomState(16)
    q = nd.array(r.randn(4, 2, 8).astype(np.float32))
    kv = nd.array(r.randn(5, 2, 8).astype(np.float32))
    with pytest.raises(mx.base.MXNetError, match="causal"):
        nd.contrib.multihead_attention(q, kv, kv, heads=2, causal=True)


def test_multihead_attention_qk_valatt_chain():
    """qk → softmax → valatt ≡ the fused op (all-valid, non-causal) —
    and the qk scores match the interleaved op's on the same content."""
    r = np.random.RandomState(13)
    L, B, H, D = 6, 2, 2, 4
    q = r.randn(L, B, H * D).astype(np.float32)
    k = r.randn(L, B, H * D).astype(np.float32)
    v = r.randn(L, B, H * D).astype(np.float32)
    att = nd.contrib.multihead_attention_qk(nd.array(q), nd.array(k),
                                            heads=H).asnumpy()
    assert att.shape == (B * H, L, L)
    p = np.exp(att - att.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    chain = nd.contrib.multihead_attention_valatt(
        nd.array(p.astype(np.float32)), nd.array(v), heads=H).asnumpy()
    fused = nd.contrib.multihead_attention(
        nd.array(q), nd.array(k), nd.array(v), heads=H).asnumpy()
    np.testing.assert_allclose(chain, fused, rtol=1e-4, atol=1e-5)
    # scores equal the interleaved op's on identically-interleaved qkv
    qkv = np.stack([q.reshape(L, B, H, D), k.reshape(L, B, H, D),
                    v.reshape(L, B, H, D)], axis=3).reshape(L, B, 3 * H * D)
    want = nd.contrib.interleaved_matmul_selfatt_qk(
        nd.array(qkv), heads=H).asnumpy()
    np.testing.assert_allclose(att, want, rtol=1e-5, atol=1e-6)


def test_multihead_attention_grads_flow():
    r = np.random.RandomState(14)
    q = nd.array(r.randn(4, 2, 8).astype(np.float32))
    k = nd.array(r.randn(4, 2, 8).astype(np.float32))
    v = nd.array(r.randn(4, 2, 8).astype(np.float32))
    for x in (q, k, v):
        x.attach_grad()
    with autograd.record():
        out = nd.contrib.multihead_attention(q, k, v, heads=2,
                                             causal=True)
        loss = (out * out).sum()
    loss.backward()
    for x in (q, k, v):
        assert np.isfinite(x.grad.asnumpy()).all()
        assert np.abs(x.grad.asnumpy()).sum() > 0
