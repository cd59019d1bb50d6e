"""perfbench/counts.py against values worked by hand."""

import json
import os

import pytest

from perfbench import counts

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _cfg(hidden_size):
    """The benchmark's configuration of that width."""
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    for entry in bench["configs"]:
        with open(os.path.join(ROOT, entry["file"])) as f:
            cfg = json.load(f)
        if cfg["hidden_size"] == hidden_size:
            return cfg
    raise LookupError(hidden_size)


@pytest.mark.parametrize("width, seq, n_matmul, flops", [
    # per layer 4*768^2 + 2*768*3072 = 7,077,888; x12 = 84,934,656;
    # decoder 768*30522 = 23,440,896
    (768, 512, 108_375_552, 6 * 108_375_552 + 12 * 12 * 768 * 512),
    (768, 128, 108_375_552, 6 * 108_375_552 + 12 * 12 * 768 * 128),
    # per layer 4*1024^2 + 2*1024*4096 = 12,582,912; x24 = 301,989,888;
    # decoder 1024*30522 = 31,254,528
    (1024, 512, 333_244_416,
     6 * 333_244_416 + 12 * 24 * 1024 * 512),
])
def test_train_flops_per_token(width, seq, n_matmul, flops):
    cfg = _cfg(width)
    assert counts.matmul_params(cfg) == n_matmul
    assert counts.train_flops_per_token(cfg, seq) == flops


def test_width_768_seq512_flops_in_plain_numbers():
    # 650,253,312 + 56,623,104
    assert counts.train_flops_per_token(_cfg(768), 512) \
        == 706_876_416


def test_attention_kernel_counts():
    # batch 32, 12 heads, seq 512, head dim 64:
    # 12 * 32 * 12 * 512^2 * 64 = 77,309,411,328 FLOPs a layer
    assert counts.attention_flops_per_layer(32, 12, 512, 64) \
        == 77_309_411_328
    # 12 tensors of 32*12*512*64 bf16 values = 301,989,888 bytes
    assert counts.attention_bytes_per_layer(32, 12, 512, 64) \
        == 301_989_888


def test_roofline_says_which_bound():
    peak = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    t, bound = counts.roofline_seconds(77_309_411_328, 301_989_888, peak)
    assert bound == "compute"
    assert t == pytest.approx(77_309_411_328 / 197e12)
    t, bound = counts.roofline_seconds(1e9, 1e9, peak)
    assert bound == "memory" and t == pytest.approx(1e9 / 819e9)


def test_peaks_table_names_its_sources():
    with open(os.path.join(ROOT, "perfbench", "peaks.json")) as f:
        peaks = json.load(f)
    assert peaks["TPU v5 lite"]["bf16_flops_per_s"] == 197e12
    assert peaks["TPU v5 lite"]["hbm_bytes_per_s"] == 819e9
    for kind, row in peaks.items():
        assert row["source"], kind
        assert row["platform"] == "tpu", kind
