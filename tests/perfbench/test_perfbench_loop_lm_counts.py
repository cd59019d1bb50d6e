"""Operations and bytes of the looped language model's step from the
configuration file, against a hand count at the published widths and the
zoo model's own parameter count, and the readers that turn a trace and the
program's counter into the cell's new metrics."""

import numpy as np
import pytest

from perfbench import counters_loop, counts
from perfbench import counts_loop_lm as loop
from perfbench import run as harness

import perfbench_tiny_loop_lm as tiny

CFG = harness.load_json(harness.HERE, "configs", "ouro_2_6b.json")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_parameters_by_part_as_the_issue_counted_them():
    assert loop.layer_matmul_params(CFG) == 4 * 2048 ** 2 + 3 * 2048 * 5632
    assert loop.layer_params(CFG) == 51_388_416
    assert loop.parameters(CFG) == 6 * 51_388_416 + 2 * 6144 * 2048 \
        + 2048 + 2049
    assert loop.parameters(CFG) == pytest.approx(333.5e6, rel=1e-4)
    assert 14 * loop.parameters(CFG) == pytest.approx(4.67e9, rel=1e-3)
    # whole, 48 layers and the published vocabulary
    whole = dict(CFG, **CFG["published"])
    assert loop.parameters(whole) == pytest.approx(2.67e9, rel=2e-3)
    assert loop.layer_applications(CFG) == 24
    assert loop.layer_applications(whole) == 4 * 48


def test_parameters_equal_the_zoo_models_own_count():
    from perfbench.builders import ouro_zoo
    from perfbench.reference import loop_lm_train as ref
    cfg = tiny.config()
    model = ouro_zoo.build_model(cfg)
    own = sum(int(np.prod(p.shape))
              for p in model.collect_params().values())
    assert loop.parameters(cfg) == own
    assert own == sum(int(np.prod(shape))
                      for shape, _init in ref.param_shapes(cfg).values())


def test_flops_a_token_by_hand():
    """24 applications of 6 x 51.38M matmul weights and 3 S H (d + d) of
    causal attention, four exits through 6,144 x 2,048 of head and the
    gate: 8.91 GFLOP a token at seq 4096; nothing made again is in it."""
    assert loop.attention_flops_per_token_layer(CFG, 4096) == \
        3 * 4096 * 16 * 256
    by_hand = 24 * (6 * (4 * 2048 ** 2 + 3 * 2048 * 5632)
                    + 3 * 4096 * 16 * 256) + 4 * 6 * 2048 * (6144 + 1)
    assert loop.train_flops_per_token(CFG, 4096) == by_hand
    assert by_hand == pytest.approx(8.91e9, rel=1e-3)
    # a step of 4,096 tokens: 36.5 TFLOP, 185 ms at the peak
    assert 4096 * by_hand / PEAK["bf16_flops_per_s"] == pytest.approx(
        0.185, rel=5e-3)
    # the head's share of the work is the published model's
    whole = dict(CFG, **CFG["published"])
    head = 4 * 6 * loop.head_params_per_token(CFG) / by_hand
    head_whole = 4 * 6 * loop.head_params_per_token(whole) \
        / loop.train_flops_per_token(whole, 4096)
    assert head == pytest.approx(0.034, abs=1e-3)
    assert head_whole == pytest.approx(head, rel=0.01)


def test_attention_work_a_layer_application():
    flops = loop.attention_flops_per_layer(CFG, 1, 4096)
    nbytes = loop.attention_bytes_per_layer(CFG, 1, 4096)
    assert flops == 3 * 16 * 4096 ** 2 * 256
    assert nbytes == 12 * 16 * 4096 * 128 * 2
    least, bound = counts.roofline_seconds(flops, nbytes, PEAK)
    assert bound == "compute" and least == pytest.approx(1.0465e-3, rel=1e-3)


def _cell(cfg=CFG, seq=4096):
    return {"config": cfg, "chips": 1, "name": "ouro_2_6b.train_s4096",
            "traffic": {"batch": 1, "seq": seq}}


def test_loop_step_mfu_reads_model_flops_over_the_peak():
    run = {"cell": _cell(), "peak": PEAK, "tokens": 10 * 4096,
           "window_s": 10 * 0.37}
    got = harness.read_metric("loop_step_mfu", run)
    assert got == pytest.approx(100 * 0.185 / 0.37, rel=5e-3)


def test_loop_flash_roofline_counts_each_application_once():
    """24 applications a step at 1.0465 ms of least time each, over the
    time of every ``flash_*`` Pallas call: the calls made again add time
    and no work, and a kernel of another name adds nothing."""
    ops = {"flash_fwd.1:tpu_custom_call": [42 * 3, 3 * 42 * 1.5e-3],
           "flash_bwd.2:tpu_custom_call": [24 * 3, 3 * 24 * 3.0e-3],
           "ragged-dot.7:tpu_custom_call": [3, 1.0],
           "fusion.9": [3, 1.0]}
    run = {"cell": _cell(), "peak": PEAK, "steps": 3,
           "trace": {"ops": {"/device:TPU:0": ops}}}
    took = 3 * (42 * 1.5e-3 + 24 * 3.0e-3)
    got = harness.read_metric("loop_flash_roofline", run)
    assert got == pytest.approx(100 * 3 * 24 * 1.0465e-3 / took, rel=1e-3)
    assert got < 100
    assert harness.read_metric("loop_flash_roofline",
                               dict(run, trace=None)) is None
    none = {"cell": _cell(), "peak": PEAK, "steps": 3,
            "trace": {"ops": {"/device:TPU:0": {"fusion.9": [3, 1.0]}}}}
    assert harness.read_metric("loop_flash_roofline", none) is None


@pytest.mark.parametrize("kept, made_again, traces, want", [
    (6, 18, 1, 42), (6, 18, 3, 42), (0, 24, 1, 48), (24, 0, 2, 24)],
    ids=["as_asked", "three_traces", "all_made_again", "none_made_again"])
def test_layer_passes_a_step_from_the_counter(kept, made_again, traces,
                                              want):
    """The counter as the model grows it, a trace of the step: one a kept
    application, two one made again."""
    from mxnet_tpu import telemetry
    telemetry.REGISTRY.reset()
    assert harness.read_metric("loop_layer_passes_per_step",
                               {"cell": _cell()}) is None
    for kind, n in (("kept", kept), ("made_again", 2 * made_again)):
        if n:
            telemetry.counter("mxnet_loop_layer_passes_total", "",
                              labels={"model": "ouro", "kind": kind}
                              ).inc(traces * n)
    assert counters_loop.layer_passes_per_step(24) == want
    assert harness.read_metric("loop_layer_passes_per_step",
                               {"cell": _cell()}) == want
    telemetry.REGISTRY.reset()


def test_loop_attn_proj_reads_its_region():
    from perfbench import scopes
    run = {"trace": {}, "_scopes_split": {
        "by_region": {"attn_proj": 0.25, "attention": 0.3}, "busy_s": 1.0,
        "mixed_s": 0.0, "steps": 5}}
    assert harness.read_metric("loop_attn_proj_ms_per_step", run) == \
        pytest.approx(50.0)
    assert scopes.region_ms_per_step(run, "attention") == pytest.approx(60.0)
    assert harness.read_metric("loop_attn_proj_ms_per_step",
                               {"trace": None}) is None
