"""Operations and bytes of the MLA + MoE step from the configuration file,
against the arithmetic the cell was sized with, and the readers that turn a
trace and the program's counters into the cell's metrics."""

import pytest

from perfbench import counts_mla_moe as counts
from perfbench import run as harness

CFG = harness.load_json(harness.HERE, "configs", "kanana_2_30b_a3b.json")


def test_parameters_a_layer():
    assert counts.attention_params(CFG) == pytest.approx(26.35e6, rel=1e-3)
    assert counts.expert_params(CFG) == 3 * 2048 * 768
    assert counts.expected_pairs_per_token(CFG) == 0.75
    # attention x5, dense FFN, 4 x (router + shared + 0.75 routed), head
    want = 5 * 26.35e6 + 37.75e6 + 4 * (0.262e6 + 9.437e6 + 3.539e6) \
        + 2048 * 16032
    assert counts.matmul_params_per_token(CFG) == pytest.approx(want,
                                                                rel=1e-3)


def test_flops_a_token_and_a_step():
    assert counts.attention_flops_per_token_layer(CFG, 4096) == \
        3 * 4096 * 32 * 320
    per_token = counts.train_flops_per_token(CFG, 4096)
    assert per_token == pytest.approx(
        6 * counts.matmul_params_per_token(CFG) + 5 * 125.8e6, rel=1e-3)
    # the issue's arithmetic: about 17.7 TFLOP a step of 8,192 tokens
    assert 8192 * per_token == pytest.approx(17.7e12, rel=0.02)
    # attention proper is a good quarter of it
    share = 5 * 125.8e6 / per_token
    assert 0.25 < share < 0.33


def test_attention_kernel_work_counts_the_causal_half_at_both_widths():
    flops = counts.attention_flops_per_layer(CFG, 2, 4096)
    assert flops == 3 * 2 * 32 * 4096 * 4096 * (192 + 128)
    nbytes = counts.attention_bytes_per_layer(CFG, 2, 4096)
    assert nbytes == 6 * 2 * 32 * 4096 * (192 + 128) * 2
    # compute bounds it: far over the chip's ridge of 240 FLOPs a byte
    assert flops / nbytes > 1000


def test_grouped_products_work_follows_the_pairs():
    assert counts.grouped_flops(CFG, 6144) == 2 * 3 * 6144 * 2048 * 768 * 3
    assert counts.grouped_flops(CFG, 0) == 0
    weights_only = counts.grouped_bytes(CFG, 0)
    assert weights_only == 3 * 3 * 16 * 2048 * 768 * 2
    assert counts.grouped_bytes(CFG, 6144) > weights_only


def _run(ops, steps=2, counters=None):
    cell = {"config": CFG, "chips": 1,
            "traffic": {"batch": 2, "seq": 4096}}
    return {"trace": {"ops": {"/device:TPU:0": ops}}, "steps": steps,
            "cell": cell, "tokens": steps * 8192, "window_s": 0.5,
            "peak": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}}


def test_flash_reader_goes_by_the_kernels_names():
    ops = {"flash_fwd.3:tpu_custom_call": [10, 0.010],
           "flash_bwd_dq.4:tpu_custom_call": [10, 0.015],
           "flash_bwd_dkv.5:tpu_custom_call": [10, 0.025],
           "ragged-dot-none.2:tpu_custom_call": [24, 0.5],
           "fusion.7": [3, 0.1]}
    value = harness.read_metric("mla_flash_roofline", _run(ops))
    least = 5 * 2 * 3 * 2 * 32 * 4096 ** 2 * 320 / 197e12
    assert value == pytest.approx(100 * least / 0.050)
    assert harness.read_metric("mla_flash_roofline",
                               _run({"fusion.7": [3, 0.1]})) is None
    assert harness.read_metric("mla_flash_roofline",
                               dict(_run(ops), trace=None)) is None


def test_flash_ms_reader_leaves_the_grouped_products_out():
    ops = {"flash_fwd.3:tpu_custom_call": [10, 0.010],
           "flash_bwd_dq.4:tpu_custom_call": [10, 0.015],
           "flash_bwd_dkv.5:tpu_custom_call": [10, 0.025],
           "ragged-dot-none.2:tpu_custom_call": [24, 0.5]}
    assert harness.read_metric("mla_flash_ms_per_step", _run(ops)) == \
        pytest.approx(1e3 * 0.050 / 2)
    # flash_ms_per_step goes by the custom-call target and counts them all
    assert harness.read_metric("flash_ms_per_step", _run(ops)) == \
        pytest.approx(1e3 * 0.550 / 2)
    assert harness.read_metric("mla_flash_ms_per_step",
                               _run({"fusion.7": [3, 0.1]})) is None
    assert harness.read_metric("mla_flash_ms_per_step",
                               dict(_run(ops), trace=None)) is None


def test_grouped_reader_needs_kernels_and_counters(monkeypatch):
    from perfbench import counters_moe
    ops = {"ragged-dot-none.2:tpu_custom_call": [24, 0.040],
           "flash_fwd.3:tpu_custom_call": [10, 0.5]}
    monkeypatch.setattr(counters_moe, "pairs_per_token", lambda: None)
    assert harness.read_metric("moe_grouped_roofline", _run(ops)) is None
    monkeypatch.setattr(counters_moe, "pairs_per_token", lambda: 0.75)
    value = harness.read_metric("moe_grouped_roofline", _run(ops))
    # at 384 tokens an expert the experts' bytes bound it, not the FLOPs
    least = max(counts.grouped_flops(CFG, 6144) / 197e12,
                counts.grouped_bytes(CFG, 6144) / 819e9)
    assert least == counts.grouped_bytes(CFG, 6144) / 819e9
    assert value == pytest.approx(100 * 4 * 2 * least / 0.040)
    assert value < 100
    assert harness.read_metric("moe_grouped_roofline",
                               _run({"fusion.1": [1, 1.0]})) is None


def test_counter_readers_are_silent_without_the_counters(monkeypatch):
    from mxnet_tpu import telemetry
    telemetry.REGISTRY.reset()
    run = _run({})
    assert harness.read_metric("moe_pairs_per_token", run) is None
    assert harness.read_metric("moe_load_max_over_mean", run) is None
    layer = {"layer": "m/layers/layer1/moe"}
    telemetry.REGISTRY.counter("mxnet_moe_pairs_total",
                               labels=layer).inc(4 * 6144)
    telemetry.REGISTRY.counter("mxnet_moe_tokens_total").inc(4 * 8192)
    telemetry.REGISTRY.gauge("mxnet_moe_expert_tokens_max",
                             labels=layer).set(480)
    assert harness.read_metric("moe_pairs_per_token", run) == 0.75
    # mean load 6144 / 16 = 384 a step and expert; the fullest held 480
    assert harness.read_metric("moe_load_max_over_mean", run) == \
        pytest.approx(480 / 384)
    telemetry.REGISTRY.reset()


def test_moe_step_mfu_uses_this_models_count():
    value = harness.read_metric("moe_step_mfu", _run({}))
    rate = 2 * 8192 / 0.5
    assert value == pytest.approx(
        100 * counts.train_flops_per_token(CFG, 4096) * rate / 197e12)


def test_allreduce_reader_sums_starts_and_dones_of_the_fullest_device():
    run = {"steps": 4, "trace": {"ops": {
        "/device:TPU:0": {"all-reduce.205": [16, 0.012],
                          "all-reduce-start.3": [16, 0.002],
                          "all-reduce-done.3": [16, 0.004],
                          "fusion.9": [4, 0.3]},
        "/device:TPU:1": {"all-reduce.205": [16, 0.010],
                          "reduce.4": [4, 0.3]}}}}
    assert harness.read_metric("allreduce_ms_per_step", run) == \
        pytest.approx(1e3 * 0.018 / 4)
    run["trace"]["ops"] = {"/device:TPU:0": {"fusion.9": [4, 0.3]}}
    assert harness.read_metric("allreduce_ms_per_step", run) is None
    assert harness.read_metric("allreduce_ms_per_step",
                               {"trace": None}) is None
