"""Operations and bytes of the hybrid Gated DeltaNet + MoE step from the
configuration file, against the arithmetic the cell was sized with and the
zoo model's own parameter count, and the readers that turn a trace and the
program's counters into the cell's new metrics."""

import numpy as np
import pytest

from perfbench import counts_gdn_moe as counts
from perfbench import counts_mla_moe
from perfbench import run as harness

import perfbench_tiny_gdn_moe as tiny

CFG = harness.load_json(harness.HERE, "configs", "qwen3_next_80b_a3b.json")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_parameters_by_part_as_the_issue_counted_them():
    assert counts.layer_kinds(CFG) == (3, 1)
    assert counts.linear_mixer_params(CFG) == pytest.approx(33.7e6, rel=1e-3)
    assert counts.full_mixer_params(CFG) == pytest.approx(27.3e6, rel=2e-3)
    assert counts_mla_moe.expert_params(CFG) == 3 * 2048 * 512
    assert counts_mla_moe.expected_pairs_per_token(CFG) == 0.625
    assert counts.parameters(CFG) == pytest.approx(626e6, rel=1e-3)
    assert 14 * counts.parameters(CFG) == pytest.approx(8.76e9, rel=1e-3)


@pytest.mark.parametrize("held", [(0, 16), (4, 8)])
def test_parameters_equal_the_zoo_models_own_count(held):
    from perfbench.builders import qwen3_next_zoo
    cfg = tiny.config(held=held)
    model = qwen3_next_zoo.build_model(cfg)
    own = sum(int(np.prod(p.shape))
              for p in model.collect_params().values())
    assert counts.parameters(cfg) == own
    from perfbench.reference import gdn_moe_train as ref
    assert own == sum(int(np.prod(shape))
                      for shape, _init in ref.param_shapes(cfg).values())


def test_flops_a_token_and_a_step():
    assert counts.attention_flops_per_token_layer(CFG, 8192) == \
        3 * 8192 * 16 * 512
    # a chunk of 64 and a head of 128/128: 8 C d + 6 d^2 a token forward
    assert counts.scan_flops_per_token_layer(CFG) == \
        3 * 32 * (8 * 64 * 128 + 6 * 128 * 128)
    per_token = counts.train_flops_per_token(CFG, 8192)
    assert per_token == pytest.approx(
        6 * counts.matmul_params_per_token(CFG) + 201.3e6 + 3 * 15.73e6,
        rel=1e-3)
    # the issue's arithmetic: 1.4 GFLOP a token
    assert per_token == pytest.approx(1.4e9, rel=0.01)
    # the scan's own products are a thirtieth of the step's
    assert 3 * counts.scan_flops_per_token_layer(CFG) / per_token < 0.04


def test_scan_and_attention_work_a_layer():
    flops = counts.scan_flops_per_layer(CFG, 1, 8192)
    nbytes = counts.scan_bytes_per_layer(CFG, 1, 8192)
    assert flops == 8192 * counts.scan_flops_per_token_layer(CFG)
    # 11 bf16 tensors of (8192, 32, 128) and six float32 (8192, 32)
    assert nbytes == 8192 * 32 * (11 * 128 * 2 + 6 * 4)
    # its bytes bound it on this chip, not its FLOPs
    assert nbytes / 819e9 > flops / 197e12
    assert counts.attention_flops_per_layer(CFG, 1, 8192) == \
        3 * 16 * 8192 * 8192 * 512
    assert counts.attention_bytes_per_layer(CFG, 1, 8192) == \
        12 * 16 * 8192 * 256 * 2


class _Module:
    def __init__(self, text):
        self._text = text

    def to_string(self):
        return self._text


_TEXT = """HloModule jit_train_steps

ENTRY %main.1 (p: f32[8]) -> f32[8] {
  %p = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(%p), kind=kLoop, calls=%fused.1, metadata={op_name="jit(train_steps)/qwen3next/layers/layer0/gdn/scan/jvp(jit(wrapper))/gdn_scan/while/body/dot_general"}
  %fusion.2 = f32[8]{0} fusion(%fusion.1), kind=kLoop, calls=%fused.2, metadata={op_name="jit(train_steps)/qwen3next/layers/layer0/gdn/in_proj/qkvz/dot_general"}
  %fusion.3 = f32[8]{0} fusion(%fusion.2), kind=kLoop, calls=%fused.3, metadata={op_name="jit(train_steps)/qwen3next/layers/layer3/attn/q_norm/mul"}
  ROOT %flash_fwd.4 = f32[8]{0} custom-call(%fusion.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_steps)/qwen3next/layers/layer3/attn/attention/flash_fwd/pallas_call"}
}
"""


def _run(ops, steps=2):
    cell = {"config": CFG, "chips": 1,
            "traffic": {"batch": 1, "seq": 8192}}
    return {"trace": {"ops": {"/device:TPU:0": ops}}, "steps": steps,
            "cell": cell, "tokens": steps * 8192, "window_s": 0.5,
            "program": {"hlo_modules": [_Module(_TEXT)]}, "peak": PEAK}


OPS = {"fusion.1": [6, 0.090], "fusion.2": [6, 0.060], "fusion.3": [2, 0.004],
       "flash_fwd.4:tpu_custom_call": [2, 0.050],
       "ragged-dot-none.9:tpu_custom_call": [24, 0.5]}


def test_region_readers_of_the_new_regions():
    run = _run(OPS)
    assert harness.read_metric("gdn_scan_ms_per_step", run) == \
        pytest.approx(45.0)
    assert harness.read_metric("gdn_proj_ms_per_step", run) == \
        pytest.approx(30.0)
    assert harness.read_metric("gated_attn_proj_ms_per_step", run) == \
        pytest.approx(2.0)
    assert harness.read_metric("attention_ms_per_step", run) == \
        pytest.approx(25.0)
    for name in ("gdn_scan_ms_per_step", "gdn_proj_ms_per_step",
                 "gated_attn_proj_ms_per_step", "gdn_scan_roofline"):
        assert harness.read_metric(name, dict(run, trace=None)) is None


def test_scan_roofline_goes_by_the_region_whatever_implements_it():
    value = harness.read_metric("gdn_scan_roofline", _run(OPS))
    least = counts.scan_bytes_per_layer(CFG, 1, 8192) / 819e9
    assert value == pytest.approx(100 * 3 * least / 0.045)
    assert 0 < value < 100
    # a program whose scan carries no such scope: silent, not zero
    bare = {k: v for k, v in OPS.items() if k != "fusion.1"}
    assert harness.read_metric("gdn_scan_roofline", _run(bare)) is None


def test_flash_readers_go_by_the_kernels_names():
    run = _run(OPS)
    # the time is the accepted reader's, by the kernels' names
    assert harness.read_metric("mla_flash_ms_per_step", run) == \
        pytest.approx(25.0)
    least = 3 * 16 * 8192 ** 2 * 512 / 197e12
    assert harness.read_metric("gated_attn_flash_roofline", run) == \
        pytest.approx(100 * 2 * least / 0.050)
    none = {"fusion.2": [1, 0.1]}
    for name in ("mla_flash_ms_per_step", "gated_attn_flash_roofline"):
        assert harness.read_metric(name, _run(none)) is None
        assert harness.read_metric(name, dict(run, trace=None)) is None


def test_hybrid_step_mfu_uses_this_models_count():
    value = harness.read_metric("hybrid_step_mfu", _run({}))
    rate = 2 * 8192 / 0.5
    assert value == pytest.approx(
        100 * counts.train_flops_per_token(CFG, 8192) * rate / 197e12)


def test_grouped_roofline_by_hand_at_the_cells_widths(monkeypatch):
    """The accepted reader with this configuration's keys: 0.625 pairs a
    token, 5,120 pairs a layer and step over 32 held experts (160 rows a
    group), four routed layers; the held experts' bytes bound it."""
    from perfbench import counters_moe
    monkeypatch.setattr(counters_moe, "pairs_per_token", lambda: 0.625)
    value = harness.read_metric(
        "moe_grouped_roofline",
        _run({"ragged-dot-none.2:tpu_custom_call": [48, 0.040]}))
    pairs = 0.625 * 8192
    flops = 2 * 3 * pairs * 2048 * 512 * 3
    nbytes = 3 * (3 * 32 * 2048 * 512 + pairs * (3 * 2048 + 3 * 512)) * 2
    assert nbytes / 819e9 > flops / 197e12
    assert value == pytest.approx(100 * 4 * 2 * (nbytes / 819e9) / 0.040)
    assert value < 100
