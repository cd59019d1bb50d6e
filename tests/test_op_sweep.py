"""Registry-WIDE operator sweep (reference
tests/python/unittest/test_operator.py breadth, SURVEY §4.1/§4.2).

Three auto-discovered tiers over every registered kernel (aliases dedup
to one sweep each, same rule as opperf):

 1. ``test_sweep_forward``: the op runs on synthesized canonical inputs
    and returns finite values.  Input synthesis REUSES opperf's table
    (benchmark/opperf) so the two stay in lockstep; an op that cannot be
    synthesized must appear in ``SYNTH_SKIP`` with a reason — silent
    drops fail the meta-test.
 2. ``test_sweep_numpy_oracle``: ops whose name is also a numpy ufunc
    are checked against numpy on the same inputs.
 3. ``test_sweep_numeric_gradient``: every differentiable op gets a
    DIRECTIONAL finite-difference check — grad . d vs
    (f(x+eps*d) - f(x-eps*d)) / 2eps along one random direction per
    input (one FD pair per input instead of per element, which is what
    makes a 300-op sweep affordable).  Non-smooth ops are skipped with
    reasons (``FD_SKIP``).
"""

import os
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd
from mxnet_tpu.ops import registry

sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmark", "opperf"))
import opperf  # noqa: E402  (the shared input-synthesis table)


def _kernels():
    seen, names = set(), []
    for n in registry.list_ops():
        if n.startswith("_"):
            # internal kernels (same rule as opperf --all): exercised via
            # their public wrappers (x / 2 -> _div_scalar, etc.)
            continue
        if n.startswith("np."):
            # the mx.np layer is thin jnp delegation with its OWN parity
            # sweep (tests/test_numpy_broad.py, ~125 cases vs numpy);
            # sweeping the delegates here would re-test jnp itself
            continue
        op_id = id(registry.get(n))
        if op_id in seen:
            continue
        seen.add(op_id)
        names.append(n)
    return names


KERNELS = _kernels()

# Structured-input synthesizers for ops the GENERIC synthesizer cannot
# drive (ISSUE 8 satellite — the SYNTH_SKIP burn-down: 30 former skips
# now run the real forward sweep).  Each entry builds fresh (args, attrs)
# per call; int-index ops get valid indices, loss heads get labels,
# optimizer update kernels get (weight, grad, state...) triples,
# sequence ops get time-major (L, B) data + per-batch lengths.
_OVERRIDE_KEYS = None  # memoized table keys: non-override calls are free


def _unit_rows(x):
    """float32 ``x`` with its last dim scaled to norm 1."""
    x = x.astype(np.float32)
    return x / np.linalg.norm(x, axis=-1, keepdims=True)


def _sweep_override(name):
    global _OVERRIDE_KEYS
    if name is not None and _OVERRIDE_KEYS is not None \
            and name not in _OVERRIDE_KEYS:
        return None
    r = np.random.RandomState(0)
    x = nd.array(np.abs(r.randn(4, 5)).astype(np.float32) + 0.5)
    idx = nd.array(np.array([0, 2, 1, 3], np.int32), dtype="int32")
    lab = nd.array(r.randint(0, 5, (4,)).astype(np.float32))
    w = nd.array(r.randn(4, 5).astype(np.float32))
    g = nd.array(r.randn(4, 5).astype(np.float32) * 0.1)
    z = lambda: nd.zeros((4, 5))  # noqa: E731 — fresh optimizer state
    slen = nd.array(np.array([3, 2, 4, 1, 2], np.float32))
    table = {
        "one_hot": lambda: ([idx], {"depth": 5}),
        "take": lambda: ([x, idx], {"axis": 0}),
        "gather_nd": lambda: ([x, nd.array(
            np.array([[0, 1, 2], [1, 2, 3]], np.int32), dtype="int32")], {}),
        "scatter_nd": lambda: ([nd.array(np.ones(3, np.float32)), nd.array(
            np.array([[0, 1, 2], [1, 2, 3]], np.int32), dtype="int32")],
            {"shape": (4, 5)}),
        "pick": lambda: ([x, nd.array(np.array([0, 1, 2, 3],
                                               np.float32))], {}),
        "Embedding": lambda: ([idx, w],
                              {"input_dim": 4, "output_dim": 5}),
        "batch_take": lambda: ([x, idx], {}),
        "boolean_mask": lambda: ([x, nd.array(
            np.array([1, 0, 1, 1], np.float32))], {}),
        "index_add": lambda: ([x, nd.array(
            np.array([[0, 2]], np.int32), dtype="int32"),
            nd.array(np.ones((2, 5), np.float32))], {}),
        "index_copy": lambda: ([x, nd.array(
            np.array([0, 2], np.int32), dtype="int32"),
            nd.array(np.ones((2, 5), np.float32))], {}),
        "ravel_multi_index": lambda: ([nd.array(
            np.array([[0, 1], [2, 3]], np.int32), dtype="int32")],
            {"shape": (4, 5)}),
        "unravel_index": lambda: ([nd.array(
            np.array([5, 11], np.int32), dtype="int32")], {"shape": (4, 5)}),
        "histogram": lambda: ([x], {"bin_cnt": 5, "range": (0.0, 3.0)}),
        "smooth_l1": lambda: ([x], {"scalar": 1.0}),
        "SequenceLast": lambda: ([x, slen], {"use_sequence_length": True}),
        "SequenceMask": lambda: ([x, slen], {"use_sequence_length": True}),
        "SequenceReverse": lambda: ([x, slen],
                                    {"use_sequence_length": True}),
        "SoftmaxOutput": lambda: ([x, lab], {}),
        "SVMOutput": lambda: ([x, lab], {}),
        "LinearRegressionOutput": lambda: ([x, w], {}),
        "MAERegressionOutput": lambda: ([x, w], {}),
        "LogisticRegressionOutput": lambda: ([x, w], {}),
        "softmax_cross_entropy": lambda: ([x, lab], {}),
        "einsum": lambda: ([x, x], {"subscripts": "ij,kj->ik"}),
        "adadelta_update": lambda: ([w, g, z(), z()], {}),
        "adagrad_update": lambda: ([w, g, z()], {"lr": 0.01}),
        "rmsprop_update": lambda: ([w, g, z()], {"lr": 0.01}),
        "signum_update": lambda: ([w, g, z()], {"lr": 0.01}),
        "nag_mom_update": lambda: ([w, g, z()], {"lr": 0.01}),
        "ftrl_update": lambda: ([w, g, z(), z()], {"lr": 0.01}),
        # ISSUE 11 satellite burn-down: 15 more former skips run the
        # real forward sweep on structured inputs
        "adamw_update": lambda: ([w, g, z(), z()], {"lr": 0.01}),
        "rmspropalex_update": lambda: ([w, g, z(), z(), z()],
                                       {"lr": 0.01}),
        "lars_update": lambda: ([w, g, z()], {"lr": 0.01}),
        "lamb_update_phase1": lambda: ([w, g, z(), z()], {"t": 1}),
        "lamb_update_phase2": lambda: ([w, g, nd.array(
            np.array([1.0], np.float32)), nd.array(
            np.array([1.0], np.float32))], {"lr": 0.01}),
        "lamb_full_update": lambda: ([w, g, z(), z()], {"lr": 0.01}),
        "ctc_loss": lambda: ([nd.array(r.randn(6, 2, 5)
                                       .astype(np.float32)),
                              nd.array(np.array([[1, 2], [2, 3]],
                                                np.float32))], {}),
        "center_loss": lambda: ([x, nd.array(
            np.array([0, 1, 2, 3], np.float32)),
            nd.array(r.randn(5, 5).astype(np.float32))], {}),
        "im2col": lambda: ([nd.array(r.randn(1, 2, 6, 6)
                                     .astype(np.float32))],
                           {"kernel": (3, 3)}),
        "col2im": lambda: ([nd.array(r.randn(1, 18, 16)
                                     .astype(np.float32))],
                           {"output_size": (6, 6), "kernel": (3, 3)}),
        "contrib.fft": lambda: ([x], {}),
        "contrib.ifft": lambda: ([nd.array(r.randn(4, 6)
                                           .astype(np.float32))], {}),
        "contrib.count_sketch": lambda: ([x, nd.array(
            np.array([0, 3, 1, 7, 2], np.float32)),
            nd.array(np.array([1, -1, 1, 1, -1], np.float32))],
            {"out_dim": 8}),
        "contrib.box_iou": lambda: ([nd.array(np.array(
            [[0.1, 0.1, 0.5, 0.5], [0.3, 0.3, 0.9, 0.8],
             [0.0, 0.2, 0.4, 0.9]], np.float32)),
            nd.array(np.array([[0.2, 0.2, 0.6, 0.6],
                               [0.5, 0.1, 0.8, 0.7]], np.float32))], {}),
        "contrib.dequantize": lambda: ([nd.array(
            np.array(r.randint(-127, 128, (4, 5)), np.int8),
            dtype="int8"),
            nd.array(np.array([-1.0], np.float32)),
            nd.array(np.array([1.0], np.float32))], {}),
        # ISSUE 12 satellite burn-down: the interleaved-attention family,
        # detection heads, STN/correlation, quantized matmuls, linalg
        # contracts, and hawkes_ll now run the real forward sweep on
        # structured inputs (layout contracts documented per entry).
        # interleaved qkv layout: (L, B, 3*H*hd), time-major
        "contrib.interleaved_matmul_selfatt_qk": lambda: (
            [nd.array(r.randn(4, 2, 24).astype(np.float32))],
            {"heads": 2}),
        "contrib.interleaved_matmul_selfatt_valatt": lambda: (
            [nd.array(r.randn(4, 2, 24).astype(np.float32)),
             nd.array(np.abs(r.randn(4, 4, 4)).astype(np.float32))],
            {"heads": 2}),
        # encdec: q (Lq, B, E), kv (Lk, B, 2E) interleaved k/v
        "contrib.interleaved_matmul_encdec_qk": lambda: (
            [nd.array(r.randn(4, 2, 8).astype(np.float32)),
             nd.array(r.randn(5, 2, 16).astype(np.float32))],
            {"heads": 2}),
        "contrib.interleaved_matmul_encdec_valatt": lambda: (
            [nd.array(r.randn(5, 2, 16).astype(np.float32)),
             nd.array(np.abs(r.randn(4, 4, 5)).astype(np.float32))],
            {"heads": 2}),
        # detection heads: anchors in corner format inside [0, 1]
        "contrib.MultiBoxPrior": lambda: (
            [nd.array(r.randn(1, 3, 4, 4).astype(np.float32))],
            {"sizes": (0.5, 0.25), "ratios": (1.0, 2.0)}),
        "contrib.MultiBoxTarget": lambda: (
            [nd.array(np.array([[[0.1, 0.1, 0.4, 0.4],
                                 [0.3, 0.3, 0.8, 0.8],
                                 [0.5, 0.1, 0.9, 0.6],
                                 [0.0, 0.5, 0.5, 1.0]]], np.float32)),
             nd.array(np.array([[[0.0, 0.12, 0.12, 0.38, 0.42],
                                 [1.0, 0.3, 0.3, 0.8, 0.75]]],
                               np.float32)),
             nd.array(np.abs(r.randn(1, 3, 4)).astype(np.float32))], {}),
        "contrib.MultiBoxDetection": lambda: (
            [nd.array(np.abs(r.rand(1, 3, 4)).astype(np.float32)),
             nd.array((r.randn(1, 16) * 0.1).astype(np.float32)),
             nd.array(np.array([[[0.1, 0.1, 0.4, 0.4],
                                 [0.3, 0.3, 0.8, 0.8],
                                 [0.5, 0.1, 0.9, 0.6],
                                 [0.0, 0.5, 0.5, 1.0]]], np.float32))],
            {}),
        # RPN proposals: cls (1, 2A, H, W), bbox (1, 4A, H, W),
        # im_info rows [h, w, scale]; A = scales x ratios
        "contrib.Proposal": lambda: (
            [nd.array(np.abs(r.rand(1, 8, 4, 4)).astype(np.float32)),
             nd.array((r.randn(1, 16, 4, 4) * 0.1).astype(np.float32)),
             nd.array(np.array([[64.0, 64.0, 1.0]], np.float32))],
            {"scales": (8, 16), "ratios": (0.5, 1.0),
             "rpn_pre_nms_top_n": 12, "rpn_post_nms_top_n": 4,
             "rpn_min_size": 1}),
        "contrib.MultiProposal": lambda: (
            [nd.array(np.abs(r.rand(2, 8, 4, 4)).astype(np.float32)),
             nd.array((r.randn(2, 16, 4, 4) * 0.1).astype(np.float32)),
             nd.array(np.array([[64.0, 64.0, 1.0],
                                [64.0, 64.0, 1.0]], np.float32))],
            {"scales": (8, 16), "ratios": (0.5, 1.0),
             "rpn_pre_nms_top_n": 12, "rpn_post_nms_top_n": 4,
             "rpn_min_size": 1}),
        # roi ops: rois rows [batch_idx, x0, y0, x1, y1] in image coords
        "contrib.roi_align": lambda: (
            [nd.array(r.randn(1, 2, 8, 8).astype(np.float32)),
             nd.array(np.array([[0, 1.0, 1.0, 5.0, 5.0],
                                [0, 2.0, 0.0, 7.0, 6.0]], np.float32))],
            {"pooled_size": (2, 2), "spatial_scale": 1.0}),
        "contrib.PSROIPooling": lambda: (
            [nd.array(r.randn(1, 8, 8, 8).astype(np.float32)),
             nd.array(np.array([[0, 1.0, 1.0, 6.0, 6.0]], np.float32))],
            {"output_dim": 2, "pooled_size": 2, "group_size": 2}),
        # STN: loc = flat affine (1, 6) rows; identity-ish transform
        "SpatialTransformer": lambda: (
            [nd.array(r.randn(1, 2, 6, 6).astype(np.float32)),
             nd.array(np.array([[1.0, 0.1, 0.0, -0.1, 1.0, 0.0]],
                               np.float32))],
            {"target_shape": (4, 4), "transform_type": "affine",
             "sampler_type": "bilinear"}),
        "Correlation": lambda: (
            [nd.array(r.randn(1, 2, 6, 6).astype(np.float32)),
             nd.array(r.randn(1, 2, 6, 6).astype(np.float32))],
            {"kernel_size": 1, "max_displacement": 1, "stride1": 1,
             "stride2": 1, "pad_size": 1}),
        "Crop": lambda: (
            [nd.array(r.randn(1, 2, 6, 6).astype(np.float32))],
            {"h_w": (4, 4), "offset": (1, 1)}),
        # quantized matmuls: int8 operands + float range scalars
        "contrib.quantized_dot": lambda: (
            [nd.array(np.array(r.randint(-127, 128, (4, 5)), np.int8),
                      dtype="int8"),
             nd.array(np.array(r.randint(-127, 128, (5, 6)), np.int8),
                      dtype="int8"),
             nd.array(np.array([-1.0], np.float32)),
             nd.array(np.array([1.0], np.float32)),
             nd.array(np.array([-2.0], np.float32)),
             nd.array(np.array([2.0], np.float32))], {}),
        "contrib.quantized_fully_connected": lambda: (
            [nd.array(np.array(r.randint(-127, 128, (4, 5)), np.int8),
                      dtype="int8"),
             nd.array(np.array(r.randint(-127, 128, (6, 5)), np.int8),
                      dtype="int8"),
             nd.array(np.array([-1.0], np.float32)),
             nd.array(np.array([1.0], np.float32)),
             nd.array(np.array([-2.0], np.float32)),
             nd.array(np.array([2.0], np.float32))],
            {"num_hidden": 6}),
        "contrib.requantize": lambda: (
            [nd.array(np.array(r.randint(-2 ** 20, 2 ** 20, (4, 5)),
                               np.int32), dtype="int32"),
             nd.array(np.array([-4.0], np.float32)),
             nd.array(np.array([4.0], np.float32))], {}),
        # linalg contracts: gemm's axpby triple, tensorinv's even-order
        # square reshape (prod(shape[:ind]) == prod(shape[ind:]))
        "linalg.gemm": lambda: (
            [nd.array(r.randn(3, 4).astype(np.float32)),
             nd.array(r.randn(4, 5).astype(np.float32)),
             nd.array(r.randn(3, 5).astype(np.float32))],
            {"alpha": 2.0, "beta": 0.5}),
        "linalg.tensorinv": lambda: (
            [nd.array((np.eye(6) + 0.1 * r.randn(6, 6))
                      .reshape(2, 3, 2, 3).astype(np.float32))],
            {"ind": 2}),
        # hawkes: lda (N, K), alpha/beta (K,), state (N, K), lags/marks
        # (N, T), valid_length (N,), max_time (N,)
        "contrib.hawkes_ll": lambda: (
            [nd.array(np.abs(r.rand(2, 3)).astype(np.float32) + 0.5),
             nd.array(np.abs(r.rand(3)).astype(np.float32) * 0.5),
             nd.array(np.abs(r.rand(3)).astype(np.float32) + 1.0),
             nd.array(np.zeros((2, 3), np.float32)),
             nd.array(np.abs(r.rand(2, 4)).astype(np.float32)),
             nd.array(np.array([[0, 1, 2, 0], [2, 1, 0, 1]], np.float32)),
             nd.array(np.array([4, 3], np.float32)),
             nd.array(np.array([5.0, 5.0], np.float32))], {}),
        # ISSUE 13 satellite burn-down: the aux-state norm ops, RNN, the
        # loss-head Softmax alias, offset/int8 convolutions, the fused
        # mp-sgd multi-tensor pair, and the fused masked-attention family
        # now run the real forward sweep on structured inputs.
        # BatchNorm contract: (data NCHW, gamma, beta, moving_mean,
        # moving_var) — train mode normalizes with BATCH stats, the
        # moving inputs are state
        "BatchNorm": lambda: (
            [nd.array(r.randn(2, 3, 4, 4).astype(np.float32)),
             nd.array((np.abs(r.rand(3)) + 0.5).astype(np.float32)),
             nd.array((r.randn(3) * 0.1).astype(np.float32)),
             nd.array(np.zeros(3, np.float32)),
             nd.array(np.ones(3, np.float32))], {}),
        "BatchNormWithReLU": lambda: (
            [nd.array(r.randn(2, 3, 4, 4).astype(np.float32)),
             nd.array((np.abs(r.rand(3)) + 0.5).astype(np.float32)),
             nd.array((r.randn(3) * 0.1).astype(np.float32)),
             nd.array(np.zeros(3, np.float32)),
             nd.array(np.ones(3, np.float32))], {}),
        # RNN: time-major (L, B, I) data, packed params, (layers, B, H)
        # initial state; single-layer rnn_tanh keeps the packing tiny
        "RNN": lambda: (
            [nd.array(r.randn(4, 2, 3).astype(np.float32)),
             nd.array((r.randn(5 * (3 + 5 + 2)) * 0.1)
                      .astype(np.float32)),
             nd.array(np.zeros((1, 2, 5), np.float32))],
            {"state_size": 5, "num_layers": 1, "mode": "rnn_tanh"}),
        # Softmax (capital) is the upstream SoftmaxOutput loss-head
        # alias: (data, label)
        "Softmax": lambda: ([x, lab], {}),
        # deformable conv: (data, offset (2*k*k ch), weight, bias)
        "contrib.DeformableConvolution": lambda: (
            [nd.array(r.randn(1, 2, 6, 6).astype(np.float32)),
             nd.array((r.randn(1, 18, 6, 6) * 0.1).astype(np.float32)),
             nd.array(r.randn(3, 2, 3, 3).astype(np.float32)),
             nd.array(np.zeros(3, np.float32))],
            {"kernel": (3, 3), "num_filter": 3, "pad": (1, 1)}),
        # int8 NCHW conv + range scalars (the quantized_dot recipe)
        "contrib.quantized_conv": lambda: (
            [nd.array(np.array(r.randint(-127, 128, (1, 2, 6, 6)),
                               np.int8), dtype="int8"),
             nd.array(np.array(r.randint(-127, 128, (3, 2, 3, 3)),
                               np.int8), dtype="int8"),
             nd.array(np.array([-1.0], np.float32)),
             nd.array(np.array([1.0], np.float32)),
             nd.array(np.array([-2.0], np.float32)),
             nd.array(np.array([2.0], np.float32))], {"pad": (1, 1)}),
        # fused mp-sgd: (w, g, w32)*K [+ m for mom] then lrs, wds arrays
        "multi_mp_sgd_update": lambda: (
            [w, g, w.astype("float32"),
             nd.array(np.array([0.01], np.float32)),
             nd.array(np.array([0.0], np.float32))],
            {"num_weights": 1}),
        "multi_mp_sgd_mom_update": lambda: (
            [w, g, z(), w.astype("float32"),
             nd.array(np.array([0.01], np.float32)),
             nd.array(np.array([0.0], np.float32))],
            {"num_weights": 1}),
        # masked attention family (dense fallback path off-TPU):
        # selfatt keeps the reference interleaved (L, B, 3*H*D) layout
        "contrib.masked_selfatt": lambda: (
            [nd.array(r.randn(4, 2, 24).astype(np.float32))],
            {"heads": 2}),
        # qkv entry: separate (B, H, L, D) tensors
        "contrib.masked_att_qkv": lambda: (
            [nd.array(r.randn(2, 2, 4, 8).astype(np.float32)),
             nd.array(r.randn(2, 2, 4, 8).astype(np.float32)),
             nd.array(r.randn(2, 2, 4, 8).astype(np.float32))], {}),
        # dropless MoE: router over 8 experts (weight in a Dense layout),
        # then the routed experts 2..4 on the router's own choices
        "contrib.moe_router": lambda: (
            [nd.array(r.randn(6, 4).astype(np.float32)),
             nd.array(r.randn(8, 4).astype(np.float32)),
             nd.array(r.randn(8).astype(np.float32))],
            {"k": 2, "scale": 2.0}),
        "contrib.moe_experts": lambda: (
            [nd.array(r.randn(6, 4).astype(np.float32)),
             nd.array(r.rand(6, 2).astype(np.float32)),
             nd.array(r.randint(0, 8, (6, 2)).astype(np.int32)),
             nd.array(r.randn(3, 4, 5).astype(np.float32)),
             nd.array(r.randn(3, 4, 5).astype(np.float32)),
             nd.array(r.randn(3, 5, 4).astype(np.float32))],
            {"first": 2}),
        # linear attention: q, k (B, L, H, Dk) normed, v (B, L, H, Dv), the
        # log-decay g <= 0 and the write strength beta (B, L, H); a row of
        # 12 positions in chunks of 8, so the state is carried and padded
        "contrib.gated_delta_rule": lambda: (
            [nd.array(_unit_rows(r.randn(2, 12, 3, 4)) / 2.0),
             nd.array(_unit_rows(r.randn(2, 12, 3, 4))),
             nd.array(r.randn(2, 12, 3, 5).astype(np.float32)),
             nd.array(-r.rand(2, 12, 3).astype(np.float32)),
             nd.array(r.rand(2, 12, 3).astype(np.float32))],
            {"chunk": 8}),
        "contrib.causal_conv1d": lambda: (
            [nd.array(r.randn(2, 9, 6).astype(np.float32)),
             nd.array(r.randn(6, 4).astype(np.float32))], {}),
        # encdec: q (Lq, B, H*D), kv (Lk, B, 2*H*D) interleaved k/v
        "contrib.masked_encdec_att": lambda: (
            [nd.array(r.randn(4, 2, 8).astype(np.float32)),
             nd.array(r.randn(5, 2, 16).astype(np.float32))],
            {"heads": 2}),
        # mha-named wrappers (ISSUE 14 satellite): separate time-major
        # (L, B, H*D) projections
        "contrib.multihead_attention_qk": lambda: (
            [nd.array(r.randn(4, 2, 8).astype(np.float32)),
             nd.array(r.randn(5, 2, 8).astype(np.float32))],
            {"heads": 2}),
        "contrib.multihead_attention_valatt": lambda: (
            [nd.array(np.abs(r.randn(4, 3, 5)).astype(np.float32)),
             nd.array(r.randn(5, 2, 8).astype(np.float32))],
            {"heads": 2}),
        "contrib.multihead_attention": lambda: (
            [nd.array(r.randn(4, 2, 8).astype(np.float32)),
             nd.array(r.randn(4, 2, 8).astype(np.float32)),
             nd.array(r.randn(4, 2, 8).astype(np.float32))],
            {"heads": 2}),
        # ISSUE 14 satellite — the LAST SYNTH_SKIP burned down: the SP
        # attention entry point, driven through its documented
        # single-device degradation (the axis name misses every mesh a
        # prior test may have left active, so the op runs the local
        # fused/dense path deterministically); the actual ring/Ulysses
        # SP numerics are parity-tested by test_ring_attention /
        # test_ulysses on real dp×sp meshes.
        "contrib.sp_att_qkv": lambda: (
            [nd.array(r.randn(2, 2, 4, 8).astype(np.float32)),
             nd.array(r.randn(2, 2, 4, 8).astype(np.float32)),
             nd.array(r.randn(2, 2, 4, 8).astype(np.float32))],
            {"axis": "sweep_no_such_axis"}),
    }
    _OVERRIDE_KEYS = frozenset(table)
    if name is None:
        return _OVERRIDE_KEYS      # the override name set, for the meta-test
    fn = table.get(name)
    return fn() if fn is not None else None


# ops the generic synthesizer cannot drive, with the reason (tier-1 skip
# list — the meta-test asserts this list only names real registry ops).
# ISSUE 14 satellite burn-down: EMPTY.  The final entry
# (contrib.sp_att_qkv, "mesh-dependent") now runs the real forward
# sweep via its _sweep_override — the op's own single-device
# degradation contract makes the sweep deterministic regardless of any
# globally active mesh, and the SP paths stay parity-tested by
# test_ring_attention/test_ulysses.  Every registered op either sweeps
# or fails the meta-test.
SYNTH_SKIP = {}


def _inputs(name):
    """(args, attrs) for an op or None — the sweep's structured-input
    override table first, then opperf's table at small shapes."""
    spec = _sweep_override(name)
    if spec is not None:
        return spec
    old_n = opperf._N
    opperf._N = 8
    try:
        spec = opperf._inputs_for(name, mx)
    finally:
        opperf._N = old_n
    if spec is not None:
        return spec
    r = np.random.RandomState(0)
    x = nd.array(np.abs(r.randn(6, 7)).astype(np.float32) + 0.5)
    op = registry.get(name)
    for args in ([x], [x, x]):
        try:
            registry.invoke(op, args, {})
            return args, {}
        except Exception:  # noqa: BLE001
            continue
    return None


def test_sweep_skip_list_is_honest():
    for name in SYNTH_SKIP:
        assert name in registry.list_ops(), \
            f"SYNTH_SKIP names unknown op {name!r}"


def test_sweep_override_table_is_honest():
    """Every structured-input override names a real registry op and is
    not ALSO skip-listed (an overridden op must actually run)."""
    names = _sweep_override(None)
    assert names, "override table unexpectedly empty"
    for name in names:
        assert name in registry.list_ops(), \
            f"_sweep_override names unknown op {name!r}"
        assert name not in SYNTH_SKIP, \
            f"{name!r} is both overridden and skip-listed"


@pytest.mark.parametrize("name", KERNELS)
def test_sweep_forward(name):
    if name in SYNTH_SKIP:
        pytest.skip(SYNTH_SKIP[name])
    spec = _inputs(name)
    if spec is None:
        pytest.fail(f"op {name!r} has no input synthesizer and is not in "
                    "SYNTH_SKIP — add an opperf override or a skip reason")
    args, attrs = spec
    out = registry.invoke(registry.get(name), list(args), dict(attrs))
    outs = out if isinstance(out, list) else [out]
    for o in outs:
        a = o.asnumpy()
        if np.issubdtype(a.dtype, np.floating):
            assert np.isfinite(a).all(), f"{name}: non-finite output"


_NUMPY_ORACLE_SKIP = {
    # mx op semantics intentionally differ from the same-named numpy fn
    "clip": "mx.clip takes a_min/a_max attrs, not positional",
    "round": "mx rounds half away from zero (reference semantics); "
             "numpy rounds half to even",
}


@pytest.mark.parametrize("name", [
    n for n in KERNELS
    if hasattr(np, n) and callable(getattr(np, n))
    and n not in SYNTH_SKIP])
def test_sweep_numpy_oracle(name):
    if name in _NUMPY_ORACLE_SKIP:
        pytest.skip(_NUMPY_ORACLE_SKIP[name])
    spec = _inputs(name)
    if spec is None:
        pytest.skip("no synthesizer (covered by test_sweep_forward policy)")
    args, attrs = spec
    if attrs:
        pytest.skip("attr-carrying op; oracle comparison not 1:1")
    np_in = [a.asnumpy() for a in args]
    try:
        want = getattr(np, name)(*np_in)
    except TypeError:
        pytest.skip("numpy signature differs")
    got = registry.invoke(registry.get(name), list(args), {})
    got = (got[0] if isinstance(got, list) else got).asnumpy()
    if not isinstance(want, np.ndarray):
        want = np.asarray(want)
    assert got.shape == want.shape or got.size == want.size, \
        f"{name}: shape {got.shape} vs numpy {want.shape}"
    np.testing.assert_allclose(got.reshape(want.shape), want,
                               rtol=2e-5, atol=2e-6, err_msg=name)


# non-smooth / non-real-gradient ops: directional FD is meaningless
FD_SKIP = {
    "sign": "piecewise-constant", "floor": "piecewise-constant",
    "ceil": "piecewise-constant", "round": "piecewise-constant",
    "rint": "piecewise-constant", "fix": "piecewise-constant",
    "trunc": "piecewise-constant",
    "abs": "kink at 0 is fine but |x| synth crosses it in FD noise",
    "topk": "selection op", "sort": "permutation op",
    "argsort": "selection op",
    "Dropout": "stochastic", "dropout": "stochastic",
    "shuffle": "stochastic",
    "LeakyReLU": "rrelu branch stochastic; leaky kink",
    "relu": "kink at 0", "hard_sigmoid": "kinks",
    "clip": "kinks at bounds",
    "erfinv": "FD ill-conditioned near synth range edges",
    "reciprocal": "FD ill-conditioned for |x| < 1",
    "rsqrt": "FD ill-conditioned near 0", "rcbrt": "FD ill-conditioned",
    "log": "FD needs strictly positive well-scaled inputs",
    "log2": "FD scale", "log10": "FD scale", "log1p": "FD scale",
    "sqrt": "FD near 0", "cbrt": "FD near 0",
    "gamma": "FD overflow on synth range",
    "gammaln": "FD scale", "digamma": "FD poles",
    "tan": "poles", "cot": "poles",
    "Pooling": "max-pool selection kinks",
    "max": "selection", "min": "selection",
    "batch_dot": "opperf shapes (batched) fine but fwd-only here",
    "norm": "kink at 0 for ord=1 path",
    "exp": "magnifies FD noise on synth range",
    "expm1": "FD scale",
    "softmax_cross_entropy": "label input",
    "where": "bool first input",
    "BlockGrad": "gradient is 0 by definition (stop-gradient op)",
    "linalg.extracttrian": "offset-attr contract",
    "mod": "kinks at multiples", "broadcast_mod": "kinks at multiples",
    "erf": "fine but |grad| tiny at synth range edges",
    "arcsin": "domain-edge sensitivity", "arccos": "domain-edge",
    "arctanh": "domain-edge", "arccosh": "domain-edge",
    "L2Normalization": "norm kink sensitivity at synth scale",
    "adam_update": "optimizer update mutates, not a math grad",
    "adadelta_update": "optimizer update", "adagrad_update": "optimizer update",
    "rmsprop_update": "optimizer update", "signum_update": "optimizer update",
    "nag_mom_update": "optimizer update", "ftrl_update": "optimizer update",
    "adamw_update": "optimizer update",
    "rmspropalex_update": "optimizer update",
    "lars_update": "optimizer update",
    "lamb_update_phase1": "optimizer update",
    "lamb_update_phase2": "optimizer update",
    "lamb_full_update": "optimizer update",
    "ctc_loss": "loss head: backward is the CTC loss grad; labels are "
                "integer selectors",
    "center_loss": "loss head with aux center update (train-mode "
                   "mutation); backward is the loss grad",
    "contrib.dequantize": "range inputs kink at |min|==|max| (max of "
                          "abs); data input is int8",
    "contrib.fft": "reference layout contract casts to float32 inside; "
                   "float64 FD precision lost (forward swept)",
    "contrib.ifft": "float32-inside cast (same as contrib.fft)",
    # loss heads: backward is the LOSS gradient by contract, not
    # d(forward)/dx — FD against the forward is meaningless
    "SoftmaxOutput": "loss head: backward = softmax - label",
    "SVMOutput": "loss head: backward = hinge grad",
    "LinearRegressionOutput": "loss head: backward = pred - label",
    "MAERegressionOutput": "loss head: backward = sign(pred - label)",
    "LogisticRegressionOutput": "loss head: backward = sigmoid - label",
    "histogram": "piecewise-constant bin counts",
    "one_hot": "int input; output independent of any float input",
    "sgd_update": "optimizer update", "sgd_mom_update": "optimizer update",
    "mp_sgd_update": "optimizer update",
    "mp_sgd_mom_update": "optimizer update",
    "multi_sgd_update": "optimizer update",
    "multi_sgd_mom_update": "optimizer update",
    "preloaded_multi_sgd_update": "optimizer update",
    "preloaded_multi_sgd_mom_update": "optimizer update",
    "amp_multicast": "dtype-cast utility (gradient is identity-cast)",
    "linalg.gelqf": "QR-based factorization; grad not defined upstream",
    "BilinearSampler": "grid-cell boundary kinks (floor of sample coords)",
    # ISSUE 12 satellite burn-down: forward now swept; backward exempt
    # with the honest reason per entry
    "contrib.roi_align": "bin-boundary kinks (bilinear sampling grid, "
                         "same class as BilinearSampler)",
    "contrib.PSROIPooling": "bin-boundary kinks (floor of roi bin edges)",
    "SpatialTransformer": "grid-cell kinks via its BilinearSampler step",
    "Correlation": "zero-padded displacement windows kink at the image "
                   "border taps",
    "contrib.quantized_dot": "int8 operands; range inputs kink at "
                             "|min|==|max| (max-of-abs)",
    "contrib.quantized_fully_connected": "int8 operands; range max-of-abs "
                                         "kinks",
    "contrib.requantize": "int32 data; round/clip staircase",
    "linalg.tensorinv": "FD through a 6x6 inverse amplifies eps by "
                        "cond^2; forward swept on a well-conditioned "
                        "operand",
    "contrib.hawkes_ll": "marks/valid_length are integer selectors and "
                         "the state output rides a scan; backward is "
                         "covered by the LL head's analytic grad in "
                         "test_contrib_ops",
    # ISSUE 13 satellite burn-down: forward now swept; backward exempt
    # with the honest reason per entry
    "Softmax": "loss head (SoftmaxOutput alias): backward = softmax - "
               "label by contract, not d(forward)/dx",
    "BatchNormWithReLU": "relu kink at 0 on top of the normalization",
    "multi_mp_sgd_update": "optimizer update",
    "multi_mp_sgd_mom_update": "optimizer update",
    "contrib.DeformableConvolution": "bilinear sampling grid kinks "
                                     "(BilinearSampler class) in the "
                                     "offset path",
    "contrib.quantized_conv": "int8 operands; range inputs kink at "
                              "|min|==|max| (max-of-abs)",
    "BatchNorm": "batch-stat normalization runs float32 on the x64-less "
                 "lattice; 1e-5-eps FD loses precision (backward "
                 "covered by test_operator/test_gluon BatchNorm tests)",
    "contrib.masked_selfatt": "softmax core float32 on the x64-less "
                              "lattice (float64 FD precision lost, the "
                              "contrib.fft class); grads parity-tested "
                              "by test_flash_attention",
    "contrib.masked_att_qkv": "float32 softmax core (same class as "
                              "masked_selfatt); test_flash_attention",
    "contrib.masked_encdec_att": "float32 softmax core (same class as "
                                 "masked_selfatt); transformer grads in "
                                 "test_model_zoo",
    "contrib.gated_delta_rule": "float32 state and sums whatever the "
                                "inputs' type (the float64 FD's precision "
                                "is lost); out and all five grads against "
                                "the token-by-token recurrence in "
                                "test_linear_attention",
    "contrib.causal_conv1d": "sums in float32 whatever the inputs' type; "
                             "grads against a windowed einsum in "
                             "test_linear_attention",
    "contrib.moe_router": "float32 router core with an integer output "
                          "(the chosen experts) and a top-k choice that "
                          "a finite difference can flip; weights and "
                          "grads pinned by test_moe",
    "contrib.moe_experts": "integer selector input (expert ids) and an "
                           "integer output (tokens per expert); grads "
                           "against finite differences and a per-token "
                           "oracle in test_moe",
    # ISSUE 14 satellite: the mha-named fused wrapper + the SP entry
    # share the masked_selfatt float32-softmax-core class; their grads
    # are covered by test_contrib_ops.test_multihead_attention_grads_flow
    # and test_ring_attention/test_ulysses respectively.  The unfused
    # qk/valatt wrappers are plain matmuls and DO run the FD sweep.
    "contrib.multihead_attention": "float32 softmax core (masked_selfatt "
                                   "class); grads in test_contrib_ops",
    "contrib.sp_att_qkv": "float32 softmax core via the degradation "
                          "path; SP grads in test_ring_attention/"
                          "test_ulysses",
}


# ops whose trailing float inputs are semantically integer SELECTORS
# (sequence lengths, pick indices): perturbing them flips the selection
# (FD explodes) while the analytic grad is correctly zero — FD checks
# only the data input
FD_DATA_INPUT_ONLY = {"SequenceLast", "SequenceMask", "SequenceReverse",
                      "pick",
                      # h (bucket indices) and s (signs) are selectors
                      "contrib.count_sketch"}


@pytest.mark.parametrize("name", [
    n for n in KERNELS
    if registry.get(n).differentiable and n not in SYNTH_SKIP
    and n not in FD_SKIP])
def test_sweep_numeric_gradient(name):
    spec = _inputs(name)
    if spec is None:
        pytest.skip("no synthesizer")
    args, attrs = spec
    float_idx = [i for i, a in enumerate(args)
                 if np.dtype(a.dtype).kind == "f"]
    if name in FD_DATA_INPUT_ONLY:
        float_idx = float_idx[:1]
    if not float_idx:
        pytest.skip("no float inputs")
    from mxnet_tpu import autograd
    op = registry.get(name)

    def f(*xs):
        out = registry.invoke(op, list(xs), dict(attrs))
        out = out[0] if isinstance(out, list) else out
        return out.astype("float64").sum()

    ins = [a.astype("float64") if i in float_idx else a
           for i, a in enumerate(args)]
    for i in float_idx:
        ins[i].attach_grad()
    with autograd.record():
        y = f(*ins)
    try:
        y.backward()
    except Exception as e:  # noqa: BLE001
        pytest.fail(f"{name}: backward raised {type(e).__name__}: {e}")
    eps = 1e-5
    r = np.random.RandomState(1)
    for i in float_idx:
        if ins[i].grad is None:
            continue
        d = r.randn(*ins[i].shape)
        d /= max(np.linalg.norm(d), 1e-12)
        xp = ins[i].asnumpy() + eps * d
        xm = ins[i].asnumpy() - eps * d
        args_p = [nd.array(xp) if j == i else ins[j]
                  for j in range(len(ins))]
        args_m = [nd.array(xm) if j == i else ins[j]
                  for j in range(len(ins))]
        fd = (float(f(*args_p).asnumpy())
              - float(f(*args_m).asnumpy())) / (2 * eps)
        an = float((ins[i].grad.asnumpy() * d).sum())
        denom = max(abs(fd), abs(an), 1e-6)
        assert abs(fd - an) / denom < 5e-3, \
            f"{name} input {i}: directional grad {an} vs FD {fd}"
