"""Operations and bytes of the hybrid Mamba-2 + attention step from the
configuration file, against a hand count at the published widths and the
zoo model's own parameter count, and the readers that turn a trace into the
cell's new metrics."""

import numpy as np
import pytest

from perfbench import counts
from perfbench import counts_ssm_hybrid as ssm
from perfbench import run as harness

import perfbench_tiny_ssm_hybrid as tiny

CFG = harness.load_json(harness.HERE, "configs", "granite_4_0_h_micro.json")
PEAK = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


def test_parameters_by_part_as_counted_by_hand():
    assert ssm.layer_kinds(CFG) == (9, 1)
    assert ssm.mamba_matmul_params(CFG) == 17_432_576 + 8_388_608
    assert ssm.conv_params(CFG) == 4352 * 4
    assert ssm.attention_matmul_params(CFG) == 10_485_760
    assert ssm.mlp_params(CFG) == 50_331_648
    mamba_layer = (17_432_576 + 21_760 + 192 + 4_096 + 8_388_608
                   + 50_331_648 + 4_096)
    assert mamba_layer == 76_182_976
    attention_layer = 10_485_760 + 50_331_648 + 4_096
    assert attention_layer == 60_821_504
    assert ssm.parameters(CFG) == 9 * mamba_layer + attention_layer \
        + 12_544 * 2048 + 2048 == 772_160_448
    # 10.81 GB of state at 14 B a parameter, 1.54 GB of gradient buffers
    assert 14 * ssm.parameters(CFG) == pytest.approx(10.81e9, rel=1e-3)
    assert 2 * ssm.parameters(CFG) == pytest.approx(1.544e9, rel=1e-3)
    whole = dict(CFG, **CFG["published"])
    assert ssm.layer_kinds(whole) == (36, 4)
    assert ssm.parameters(whole) == pytest.approx(3.19e9, rel=2e-3)


def test_parameters_equal_the_zoo_models_own_count():
    from perfbench.builders import granite_zoo
    from perfbench.reference import ssm_hybrid_train as ref
    cfg = tiny.config()
    model = granite_zoo.build_model(cfg)
    own = sum(int(np.prod(p.shape))
              for p in model.collect_params().values())
    assert ssm.parameters(cfg) == own
    assert own == sum(int(np.prod(shape))
                      for shape, _init in ref.param_shapes(cfg).values())


def test_flops_a_token_by_hand():
    """Forward 1.60 GFLOP a token: 9 Mamba-2 layers of 156.6 MFLOP (the
    scan 4.26 of it), the attention layer 138.4, the tied head 51.4;
    training three times that, 4.80; nothing made again is in it."""
    scan = 2 * 256 * 128 + 64 * (2 * 256 * 64 + 4 * 64 * 128)
    assert ssm.scan_flops_per_token_layer(CFG) == 3 * scan == 3 * 4_259_840
    mamba = 2 * (17_432_576 + 8_388_608 + 4352 * 4 + 50_331_648) + scan
    attention = 2 * (10_485_760 + 50_331_648) + 2 * 4096 * 32 * 64
    head = 2 * 2048 * 12_544
    assert mamba == pytest.approx(156.6e6, rel=1e-3)
    assert attention == pytest.approx(138.4e6, rel=1e-3)
    assert head == pytest.approx(51.4e6, rel=1e-3)
    forward = 9 * mamba + attention + head
    assert ssm.train_flops_per_token(CFG, 4096) == 3 * forward
    assert 3 * forward == pytest.approx(4.80e9, rel=1e-3)
    # a step of 4,096 tokens: 19.65 TFLOP, 99.8 ms at the peak
    assert 4096 * 3 * forward / PEAK["bf16_flops_per_s"] == pytest.approx(
        0.0998, rel=1e-3)
    # the head weighs half its share in the whole model
    whole = dict(CFG, **CFG["published"])
    share = 3 * head / (3 * forward)
    share_whole = 6 * ssm.head_params_per_token(whole) \
        / ssm.train_flops_per_token(whole, 4096)
    assert share == pytest.approx(0.032, abs=1e-3)
    assert share_whole == pytest.approx(0.062, abs=1e-3)


def test_the_scans_work_a_layer():
    flops = ssm.scan_flops_per_layer(CFG, 1, 4096)
    nbytes = ssm.scan_bytes_per_layer(CFG, 1, 4096)
    assert flops == 4096 * 3 * 4_259_840
    assert nbytes == 4096 * ((5 * 4096 + 6 * 128) * 2 + 3 * 64 * 4)
    least, bound = counts.roofline_seconds(flops, nbytes, PEAK)
    assert bound == "compute" and least == pytest.approx(0.2657e-3, rel=1e-3)


def _cell(cfg=CFG, seq=4096):
    return {"config": cfg, "chips": 1,
            "name": "granite_4_0_h_micro.train_s4096",
            "traffic": {"batch": 1, "seq": seq}}


def test_ssm_step_mfu_reads_model_flops_over_the_peak():
    run = {"cell": _cell(), "peak": PEAK, "tokens": 10 * 4096,
           "window_s": 10 * 0.25}
    got = harness.read_metric("ssm_step_mfu", run)
    assert got == pytest.approx(100 * 0.0998 / 0.25, rel=1e-3)


def test_the_scan_and_projection_readers_read_their_regions():
    split = {"by_region": {"ssd_scan": 0.27, "ssm_proj": 0.9,
                           "attention": 0.1},
             "busy_s": 2.5, "mixed_s": 0.0, "steps": 10}
    run = {"cell": _cell(), "peak": PEAK, "trace": {},
           "_scopes_split": split}
    assert harness.read_metric("ssd_scan_ms_per_step", run) == \
        pytest.approx(27.0)
    assert harness.read_metric("ssm_proj_ms_per_step", run) == \
        pytest.approx(90.0)
    # 9 layers x 0.2657 ms of least time over 27 ms: 8.86%
    assert harness.read_metric("ssd_scan_roofline", run) == pytest.approx(
        100 * 9 * 0.2657 / 27.0, rel=1e-3)
    for name in ("ssd_scan_ms_per_step", "ssm_proj_ms_per_step",
                 "ssd_scan_roofline"):
        assert harness.read_metric(name, {"cell": _cell(), "peak": PEAK,
                                          "trace": None}) is None
    # a program without the scan's region: the roofline is silent
    none = dict(run, _scopes_split=dict(split, by_region={"attention": 0.1}))
    assert harness.read_metric("ssd_scan_roofline", none) is None
