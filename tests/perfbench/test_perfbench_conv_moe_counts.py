"""Operations and bytes of the short-convolution + attention MoE step from
the configuration file, against a hand count at the published widths and
the zoo model's own parameter count, and the readers that turn a trace and
the program's counters into the cell's new metrics."""

import numpy as np
import pytest

from perfbench import counts
from perfbench import counts_conv_moe as conv
from perfbench import run as harness

import perfbench_tiny_conv_moe as tiny

CFG = harness.load_json(harness.HERE, "configs", "lfm2_24b_a2b.json")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW = ("short_conv_ms_per_step", "gated_conv_roofline", "conv_moe_step_mfu",
       "short_conv_passes_per_step")


def test_parameters_by_part_as_counted_by_hand():
    assert conv.layer_kinds(CFG) == ["conv", "full_attention", "conv",
                                     "conv", "conv"]
    assert conv.conv_layers(CFG) == 4
    conv_op = 12_582_912 + 6_144 + 4_194_304
    assert conv_op == conv.conv_matmul_params(CFG) + conv.conv_taps(CFG) \
        == 16_783_360
    attention = 4_194_304 + 2 * 1_048_576 + 4_194_304
    assert conv.attention_matmul_params(CFG) == attention
    assert conv.dense_params(CFG) == 72_351_744
    assert conv.expert_params(CFG) == 9_437_184
    routed = 8 * 9_437_184 + 131_072
    first = conv_op + 72_351_744 + 4_096
    assert first == 89_139_200
    attention_layer = attention + 128 + routed + 4_096
    assert attention_layer == 86_118_528
    conv_layer = conv_op + routed + 4_096
    assert conv_layer == 92_416_000
    trained = first + attention_layer + 3 * conv_layer + 16_777_216 + 2_048
    assert trained == 469_284_992
    # and the expert bias of the four expert layers, 64 a layer
    assert conv.parameters(CFG) == trained + 4 * 64
    # 6.57 GB of state at 14 B a parameter, 0.94 GB of gradient buffers
    assert 14 * trained == pytest.approx(6.57e9, rel=1e-3)
    assert 16 * trained == pytest.approx(7.51e9, rel=1e-3)


def test_parameters_equal_the_zoo_models_own_count():
    from perfbench.builders import lfm2_zoo
    from perfbench.reference import conv_moe_train as ref
    cfg = tiny.config(held=(0, 8))
    model = lfm2_zoo.build_model(cfg)
    own = sum(int(np.prod(p.shape))
              for p in model.collect_params().values())
    assert conv.parameters(cfg) == own
    assert own == sum(int(np.prod(shape))
                      for shape, _init in ref.param_shapes(cfg).values())


def test_flops_a_token_by_hand():
    """Forward 405.8 MFLOP a token: four short convolutions of 33.6, the
    attention layer's products 21.0 and its causal attention 33.6 at
    8,192, the dense SwiGLU 144.7, four routers 1.05 and half a routed
    expert's 9.4 a layer, the tied head 33.6; training three times that,
    1.218 GFLOP; nothing made again is in it."""
    conv_op = 2 * (4 * 2048 ** 2 + 2048 * 3)
    attention = 2 * 10_485_760 + 2 * 8192 * 32 * 64
    dense = 2 * 72_351_744
    expert_layer = 2 * (2048 * 64 + 0.5 * 9_437_184)
    head = 2 * 2048 * 8192
    forward = 4 * conv_op + attention + dense + 4 * expert_layer + head
    assert forward == pytest.approx(405.8e6, rel=1e-3)
    assert conv.expected_pairs_per_token(CFG) == 0.5
    assert conv.train_flops_per_token(CFG, 8192) == pytest.approx(
        3 * forward, rel=1e-12)
    # a step of 32,768 tokens: 39.9 TFLOP, 202.5 ms at the peak
    assert 32768 * 3 * forward / PEAK["bf16_flops_per_s"] == pytest.approx(
        0.2025, rel=1e-3)


def test_the_gated_convolutions_work_a_layer():
    tokens = 4 * 8192
    nbytes = conv.gated_conv_bytes(CFG, tokens)
    assert nbytes == (11 * tokens * 2048 + 3 * 2048 * 3) * 2
    assert nbytes == pytest.approx(1.476e9, rel=1e-3)
    least, bound = counts.roofline_seconds(
        conv.gated_conv_flops(CFG, tokens), nbytes, PEAK)
    assert bound == "memory" and least == pytest.approx(1.803e-3, rel=1e-3)


def _cell(cfg=CFG):
    return {"config": cfg, "chips": 1, "name": "lfm2_24b_a2b.train_s8192",
            "traffic": {"batch": 4, "seq": 8192}}


def test_conv_moe_step_mfu_reads_model_flops_over_the_peak():
    run = {"cell": _cell(), "peak": PEAK, "tokens": 10 * 32768,
           "window_s": 10 * 0.5}
    got = harness.read_metric("conv_moe_step_mfu", run)
    assert got == pytest.approx(100 * 0.2025 / 0.5, rel=1e-3)


def test_the_convolution_readers_read_their_regions():
    split = {"by_region": {"gated_conv": 0.3, "short_conv": 1.2,
                           "attention": 0.4},
             "busy_s": 5.0, "mixed_s": 0.0, "steps": 10}
    run = {"cell": _cell(), "peak": PEAK, "trace": {},
           "_scopes_split": split}
    assert harness.read_metric("short_conv_ms_per_step", run) == \
        pytest.approx(120.0)
    # 4 layers x 1.803 ms of least time over 30 ms: 24.0%
    assert harness.read_metric("gated_conv_roofline", run) == pytest.approx(
        100 * 4 * 1.803 / 30.0, rel=1e-3)
    for name in ("short_conv_ms_per_step", "gated_conv_roofline"):
        assert harness.read_metric(name, {"cell": _cell(), "peak": PEAK,
                                          "trace": None}) is None
    # a program without the op's region: the roofline is silent
    none = dict(run, _scopes_split=dict(split, by_region={"attention": 0.1}))
    assert harness.read_metric("gated_conv_roofline", none) is None


@pytest.mark.parametrize("kept, made_again, want", [
    ({}, {"a": 2, "b": 2, "c": 2, "d": 2}, 8.0),        # one trace, all made again
    ({}, {"a": 4, "b": 4, "c": 4, "d": 4}, 8.0),        # two traces
    ({"a": 1, "b": 1, "c": 1, "d": 1}, {}, 4.0),        # all kept
])
def test_passes_a_step_from_the_counter(monkeypatch, kept, made_again, want):
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import metrics as registry_module
    registry = registry_module.MetricsRegistry()
    for kind, by_layer in (("kept", kept), ("made_again", made_again)):
        for layer, n in by_layer.items():
            registry.counter("mxnet_short_conv_passes_total",
                             labels={"layer": layer, "kind": kind}).inc(n)
    monkeypatch.setattr(telemetry, "REGISTRY", registry)
    assert harness.read_metric("short_conv_passes_per_step",
                               {"cell": _cell()}) == want


def test_the_passes_reader_is_silent_without_the_counter(monkeypatch):
    """The parent of the PR that brought the counter keeps none."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.telemetry import metrics as registry_module
    monkeypatch.setattr(telemetry, "REGISTRY",
                        registry_module.MetricsRegistry())
    assert harness.read_metric("short_conv_passes_per_step",
                               {"cell": _cell()}) is None
