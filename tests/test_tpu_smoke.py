"""On-chip consistency lane (SURVEY §4.2 GPU-suite trick).

Run with ``MXNET_TEST_DEVICE=tpu python -m pytest tests/test_tpu_smoke.py``
on a machine with a TPU (one process: it owns the chip): conftest then
leaves the TPU platform active and these tests cross-check every kernel
against the CPU backend — ``check_consistency(cpu, tpu)``, the universal
kernel oracle.

Skipped on the CPU-only test platform (the rest of the suite).
"""

import os

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu.test_utils import check_consistency

pytestmark = pytest.mark.skipif(
    os.environ.get("MXNET_TEST_DEVICE") != "tpu",
    reason="TPU smoke lane: set MXNET_TEST_DEVICE=tpu on the chip")


def _ctxs():
    return [mx.cpu(), mx.tpu()]


def test_tpu_visible():
    assert mx.context.num_tpus() >= 1
    a = mx.nd.ones((2, 2), ctx=mx.tpu())
    assert a.ctx.device_type == "tpu"
    assert {d.platform for d in a._data.devices()} == {"tpu"}


@pytest.mark.parametrize("op,shapes", [
    (lambda a, b: mx.nd.dot(a, b), [(8, 16), (16, 4)]),
    (lambda a, b: mx.nd.broadcast_add(a, b), [(4, 5), (1, 5)]),
    (lambda a, b: a * b + 2, [(3, 3), (3, 3)]),
    (lambda a, b: mx.nd.batch_dot(a, b), [(2, 3, 4), (2, 4, 5)]),
])
def test_binary_kernels_cpu_vs_tpu(op, shapes):
    r = np.random.RandomState(0)
    ins = [r.randn(*s).astype(np.float32) for s in shapes]
    check_consistency(op, ins, ctx_list=_ctxs(), rtol=2e-2, atol=2e-3)


@pytest.mark.parametrize("op,shape", [
    (lambda a: mx.nd.softmax(a, axis=-1), (6, 10)),
    (lambda a: mx.nd.log_softmax(a, axis=-1), (6, 10)),
    (lambda a: mx.nd.relu(a), (4, 4)),
    (lambda a: mx.nd.sigmoid(a), (4, 4)),
    (lambda a: mx.nd.tanh(a), (4, 4)),
    (lambda a: mx.nd.exp(a), (4, 4)),
    (lambda a: mx.nd.sum(a, axis=1), (5, 7)),
    (lambda a: mx.nd.max(a, axis=0), (5, 7)),
    (lambda a: mx.nd.LayerNorm(a, mx.nd.ones((7,)), mx.nd.zeros((7,))),
     (5, 7)),
    (lambda a: mx.nd.transpose(a), (3, 8)),
    (lambda a: mx.nd.topk(a, k=3, axis=-1, ret_typ="value"), (4, 9)),
])
def test_unary_kernels_cpu_vs_tpu(op, shape):
    r = np.random.RandomState(1)
    # LayerNorm closure builds params on the default ctx; rebuild per ctx
    ins = [r.randn(*shape).astype(np.float32)]
    outs = []
    for ctx in _ctxs():
        with mx.Context(ctx):
            a = mx.nd.array(ins[0], ctx=ctx)
            outs.append(np.asarray(op(a).asnumpy()))
    np.testing.assert_allclose(outs[0], outs[1], rtol=2e-2, atol=2e-3)


def test_conv_bn_cpu_vs_tpu():
    r = np.random.RandomState(2)
    x = r.randn(2, 3, 16, 16).astype(np.float32)
    w = r.randn(8, 3, 3, 3).astype(np.float32)

    def f(xa, wa):
        return mx.nd.Convolution(xa, wa, kernel=(3, 3), pad=(1, 1),
                                 num_filter=8, no_bias=True)

    check_consistency(f, [x, w], ctx_list=_ctxs(), rtol=2e-2, atol=2e-3)


def test_grad_cpu_vs_tpu():
    r = np.random.RandomState(3)
    xn = r.randn(4, 6).astype(np.float32)
    wn = r.randn(6, 2).astype(np.float32)
    grads = []
    for ctx in _ctxs():
        w = mx.nd.array(wn, ctx=ctx)
        w.attach_grad()
        x = mx.nd.array(xn, ctx=ctx)
        with autograd.record():
            loss = mx.nd.softmax_cross_entropy(
                mx.nd.dot(x, w), mx.nd.array([0, 1, 0, 1], ctx=ctx))
        loss.backward()
        grads.append(w.grad.asnumpy())
    np.testing.assert_allclose(grads[0], grads[1], rtol=2e-2, atol=2e-3)


def test_gluon_train_step_cpu_vs_tpu():
    from mxnet_tpu import gluon
    # per-ctx RNG streams differ by design (reference: per-device seeds),
    # so draw the params ONCE host-side and load them into both runs
    rp = np.random.RandomState(11)
    w0 = (rp.randn(4, 8) * 0.3).astype(np.float32)
    b0 = np.zeros((4,), np.float32)
    losses = {}
    for ctx in _ctxs():
        net = gluon.nn.Dense(4, in_units=8)
        net.initialize(ctx=ctx)
        net.weight.set_data(mx.nd.array(w0, ctx=ctx))
        net.bias.set_data(mx.nd.array(b0, ctx=ctx))
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1})
        lf = gluon.loss.SoftmaxCrossEntropyLoss()
        r = np.random.RandomState(5)
        x = mx.nd.array(r.randn(8, 8).astype(np.float32), ctx=ctx)
        y = mx.nd.array(r.randint(0, 4, (8,)), ctx=ctx)
        cur = []
        for _ in range(3):
            with autograd.record():
                loss = lf(net(x), y)
            loss.backward()
            tr.step(8)
            cur.append(float(loss.mean().asnumpy()))
        losses[str(ctx)] = cur
    vals = list(losses.values())
    np.testing.assert_allclose(vals[0], vals[1], rtol=2e-2, atol=2e-3)


def test_tpu_int8_quantized_fc_consistency():
    """INT8 path produces identical quantized results cpu-vs-tpu (integer
    arithmetic — results are exact, not approximate)."""
    r = np.random.RandomState(12)
    x = r.randn(32, 64).astype(np.float32)
    w = (r.randn(16, 64) * 0.4).astype(np.float32)
    outs = {}
    for ctx in _ctxs():
        nd = mx.nd
        qx, xmin, xmax = nd.contrib.quantize_v2(nd.array(x, ctx=ctx))
        qw, wmin, wmax = nd.contrib.quantize_v2(nd.array(w, ctx=ctx))
        o32, omin, omax = nd.contrib.quantized_fully_connected(
            qx, qw, xmin, xmax, wmin, wmax)
        outs[str(ctx)] = nd.contrib.dequantize(o32, omin, omax).asnumpy()
    vals = list(outs.values())
    np.testing.assert_allclose(vals[0], vals[1], rtol=1e-5, atol=1e-6)


def test_tpu_ctc_loss_consistency():
    r = np.random.RandomState(13)
    logits = r.randn(12, 2, 6).astype(np.float32)
    label = np.array([[1, 2, 3, 0], [4, 2, 0, 0]], np.float32)
    outs = {}
    for ctx in _ctxs():
        outs[str(ctx)] = mx.nd.ctc_loss(
            mx.nd.array(logits, ctx=ctx),
            mx.nd.array(label, ctx=ctx)).asnumpy()
    vals = list(outs.values())
    np.testing.assert_allclose(vals[0], vals[1], rtol=1e-4, atol=1e-5)


def test_tpu_deformable_conv_consistency():
    r = np.random.RandomState(14)
    x = r.randn(1, 3, 8, 8).astype(np.float32)
    w = r.randn(4, 3, 3, 3).astype(np.float32)
    off = (r.randn(1, 18, 6, 6) * 0.5).astype(np.float32)
    outs = {}
    for ctx in _ctxs():
        outs[str(ctx)] = mx.nd.contrib.DeformableConvolution(
            mx.nd.array(x, ctx=ctx), mx.nd.array(off, ctx=ctx),
            mx.nd.array(w, ctx=ctx), kernel=(3, 3),
            num_filter=4).asnumpy()
    vals = list(outs.values())
    np.testing.assert_allclose(vals[0], vals[1], rtol=1e-3, atol=1e-4)


def test_tpu_flash_attention_consistency():
    """flash ≡ dense numerics ON THE CHIP.

    On the tpu ctx, contrib.masked_selfatt lowers to the in-house Pallas
    flash kernel (kernels/flash_attention.py); on the cpu ctx the same op
    lowers to the dense fp32 path.  Agreement across the two ctxs is the
    flash-vs-dense oracle running where it matters: a kernel the chip's
    compiler refuses raises here, nothing falls back to dense."""
    L, B, H, D = 256, 2, 4, 64
    r = np.random.RandomState(21)
    qkv = (r.randn(L, B, 3 * H * D) * 0.3).astype(np.float32)
    vl = np.array([200, 256], np.float32)
    outs = {}
    for ctx in _ctxs():
        outs[str(ctx)] = mx.nd.contrib.masked_selfatt(
            mx.nd.array(qkv, ctx=ctx), mx.nd.array(vl, ctx=ctx),
            heads=H).asnumpy()
    import jax
    assert jax.default_backend() == "tpu", \
        "smoke lane expected the TPU backend, got " + jax.default_backend()
    vals = list(outs.values())
    # valid q rows only: pad rows are defined (pad attends pad) but noisy
    mask = (np.arange(L)[:, None, None] < vl[None, :, None])
    np.testing.assert_allclose(vals[0] * mask, vals[1] * mask,
                               rtol=5e-2, atol=5e-3)


def test_tpu_flash_attention_grad_consistency():
    """Custom-VJP flash backward ≡ dense autodiff backward on the chip,
    causal + GQA via masked_att_qkv (the llama path)."""
    r = np.random.RandomState(22)
    B, Hq, Hkv, L, D = 2, 4, 2, 256, 64   # L >= 256: the flash floor
    qn = (r.randn(B, Hq, L, D) * 0.3).astype(np.float32)
    kn = (r.randn(B, Hkv, L, D) * 0.3).astype(np.float32)
    vn = (r.randn(B, Hkv, L, D) * 0.3).astype(np.float32)
    vl = np.array([100, 128], np.float32)
    grads = {}
    for ctx in _ctxs():
        q = mx.nd.array(qn, ctx=ctx)
        k = mx.nd.array(kn, ctx=ctx)
        v = mx.nd.array(vn, ctx=ctx)
        for t in (q, k, v):
            t.attach_grad()
        # mask pad rows OUT of the loss: flash hard-masks pads while dense
        # soft-masks (-1e9), so pad-position outputs/grads differ by design
        # and say nothing about the kernel (same reason the forward test
        # compares valid rows only)
        wmask = mx.nd.array(
            (np.arange(L)[None, None, :, None] < vl[None, :, None, None])
            .astype(np.float32).transpose(1, 0, 2, 3), ctx=ctx)
        with autograd.record():
            out = mx.nd.contrib.masked_att_qkv(
                q, k, v, mx.nd.array(vl, ctx=ctx),
                num_kv_groups=Hq // Hkv, causal=True)
            loss = (out * out * wmask).sum()
        loss.backward()
        grads[str(ctx)] = [t.grad.asnumpy() for t in (q, k, v)]
    a, b = list(grads.values())
    vmask = (np.arange(L)[None, None, :, None] < vl[:, None, None, None])
    for name, ga, gb in zip("qkv", a, b):
        np.testing.assert_allclose(ga * vmask, gb * vmask,
                                   rtol=5e-2, atol=5e-2,
                                   err_msg=f"d{name} mismatch")


def test_tpu_flash_attention_value_width_streaming_consistency():
    """Latent attention's shapes on the chip: 192-wide queries and keys,
    128-wide values, causal, the benchmark cell's seq 4096 in blocks of
    512, so the two STREAMING kernels (flash_fwd and the one backward,
    flash_bwd, with dq for all 4,096 query rows of a head kept in VMEM
    across its 8 columns) run, forward and backward, against the dense path
    (``_dense_sdpa``) on the cpu ctx."""
    r = np.random.RandomState(23)
    B, H, L, D, Dv = 1, 2, 4096, 192, 128
    qn = (r.randn(B, H, L, D) * 0.3).astype(np.float32)
    kn = (r.randn(B, H, L, D) * 0.3).astype(np.float32)
    vn = (r.randn(B, H, L, Dv) * 0.3).astype(np.float32)
    wn = r.randn(B, H, L, Dv).astype(np.float32)
    outs, grads = {}, {}
    for ctx in _ctxs():
        q, k, v = (mx.nd.array(a, ctx=ctx) for a in (qn, kn, vn))
        for t in (q, k, v):
            t.attach_grad()
        with autograd.record():
            out = mx.nd.contrib.masked_att_qkv(q, k, v, None, causal=True)
            loss = (out * mx.nd.array(wn, ctx=ctx)).sum()
        loss.backward()
        outs[str(ctx)] = out.asnumpy()
        grads[str(ctx)] = [t.grad.asnumpy() for t in (q, k, v)]
    (oa, ob), (a, b) = outs.values(), grads.values()
    assert oa.shape == (B, H, L, Dv)
    np.testing.assert_allclose(oa, ob, rtol=5e-2, atol=5e-3)
    for name, ga, gb in zip("qkv", a, b):
        assert ga.shape == gb.shape
        np.testing.assert_allclose(ga, gb, rtol=5e-2, atol=5e-2,
                                   err_msg=f"d{name} mismatch")


def test_tpu_moe_experts_consistency():
    """The routed experts' grouped products (the TPU compiler's own Mosaic
    calls for ``ragged_dot``) against the cpu ctx's masked dense products,
    forward and backward, with an even router and with every token planted
    on one held expert: on the chip the rows past the held pairs are never
    written, which only a run there shows."""
    r = np.random.RandomState(24)
    N, U, I, E, held, k = 1024, 256, 128, 32, 4, 4
    xn = r.randn(N, U).astype(np.float32)
    rw = (r.randn(E, U) * 0.05).astype(np.float32)
    mats = [(r.randn(held, U, I) * 0.05).astype(np.float32),
            (r.randn(held, U, I) * 0.05).astype(np.float32),
            (r.randn(held, I, U) * 0.05).astype(np.float32)]
    for bias_on in (0.0, 100.0):
        rb = np.zeros(E, np.float32)
        rb[9] = bias_on
        res = {}
        for ctx in _ctxs():
            x = mx.nd.array(xn, ctx=ctx)
            ws = [mx.nd.array(m, ctx=ctx) for m in mats]
            for t in [x] + ws:
                t.attach_grad()
            weights, experts = mx.nd.contrib.moe_router(
                x, mx.nd.array(rw, ctx=ctx), mx.nd.array(rb, ctx=ctx), k=k,
                scale=2.0)
            with autograd.record():
                y, tokens, _windows = mx.nd.contrib.moe_experts(
                    x, weights, experts, *ws, first=8)
                loss = (y * y).sum()
            loss.backward()
            res[str(ctx)] = ([y.asnumpy(), x.grad.asnumpy()]
                             + [w.grad.asnumpy() for w in ws],
                             tokens.asnumpy())
        (a, ta), (b, tb) = res.values()
        assert np.array_equal(ta, tb)
        assert (tb[1] == N) == bool(bias_on)
        for name, ga, gb in zip(("y", "dx", "dgate", "dup", "ddown"), a, b):
            assert np.isfinite(gb).all(), name
            np.testing.assert_allclose(ga, gb, rtol=5e-2,
                                       atol=5e-2 * np.abs(ga).max(),
                                       err_msg=f"{name} mismatch")


def test_tpu_sparse_dot_consistency():
    """csr SpMM kernel (gather + segment-sum) cpu-vs-tpu."""
    from mxnet_tpu.ndarray import sparse
    r = np.random.RandomState(31)
    d = r.randn(8, 12).astype(np.float32)
    d[r.rand(8, 12) > 0.35] = 0.0
    rhs_np = r.randn(12, 5).astype(np.float32)   # ONE draw for both ctxs
    outs = {}
    for ctx in _ctxs():
        with mx.context.Context(ctx):
            csr = sparse.csr_matrix(d, ctx=ctx)
            rhs = mx.nd.array(rhs_np, ctx=ctx)
            outs[str(ctx)] = sparse.dot(csr, rhs).asnumpy()
    vals = list(outs.values())
    np.testing.assert_allclose(vals[0], vals[1], rtol=2e-2, atol=2e-3)
    np.testing.assert_allclose(vals[0], d @ rhs_np, rtol=2e-2, atol=2e-3)


def test_tpu_multi_sgd_consistency():
    """Fused multi-tensor update matches singles ON THE CHIP."""
    r = np.random.RandomState(33)
    ws = [r.randn(6, 4).astype(np.float32) for _ in range(3)]
    gs = [r.randn(6, 4).astype(np.float32) for _ in range(3)]
    lrs = np.array([0.1, 0.05, 0.2], np.float32)
    wds = np.array([0.0, 0.01, 0.0], np.float32)
    outs = {}
    for ctx in _ctxs():
        ins = [x for w, g in zip(ws, gs)
               for x in (mx.nd.array(w, ctx=ctx), mx.nd.array(g, ctx=ctx))]
        res = mx.nd.multi_sgd_update(
            *ins, mx.nd.array(lrs, ctx=ctx), mx.nd.array(wds, ctx=ctx),
            rescale_grad=1.0, num_weights=3)
        outs[str(ctx)] = [o.asnumpy() for o in res]
    a, b = list(outs.values())
    for x, y in zip(a, b):
        np.testing.assert_allclose(x, y, rtol=1e-5, atol=1e-6)


def test_tpu_flash_encdec_attention_consistency():
    """Cross-attention (contrib.masked_encdec_att, r5 — the MT decoder's
    fused op) flash ≡ dense ON THE CHIP, with Lq != Lk and source-padding
    masking via the kernel's separate seg_q/seg_kv inputs."""
    Lq, Lk, B, H, D = 256, 512, 2, 4, 64
    r = np.random.RandomState(23)
    q = (r.randn(Lq, B, H * D) * 0.3).astype(np.float32)
    kv = (r.randn(Lk, B, 2 * H * D) * 0.3).astype(np.float32)
    vl = np.array([400, 512], np.float32)
    outs = {}
    for ctx in _ctxs():
        outs[str(ctx)] = mx.nd.contrib.masked_encdec_att(
            mx.nd.array(q, ctx=ctx), mx.nd.array(kv, ctx=ctx),
            mx.nd.array(vl, ctx=ctx), heads=H).asnumpy()
    vals = list(outs.values())
    np.testing.assert_allclose(vals[0], vals[1], rtol=5e-2, atol=5e-3)
