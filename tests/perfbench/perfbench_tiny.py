"""A cell small enough for the CPU suite: the benchmark's first
configuration cut to the zoo's tiny preset (3 layers, 128 wide, 2 heads)
with a short vocabulary, driven through the benchmark's own harness.  The
benchmark's cells keep the published sizes; only the tests use this."""

import copy
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def bench():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)


def config(dtype="float32"):
    with open(os.path.join(ROOT, bench()["configs"][0]["file"])) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=128, num_hidden_layers=3, num_attention_heads=2,
               intermediate_size=512, vocab_size=512,
               max_position_embeddings=64)
    cfg["run"] = copy.deepcopy(cfg["run"])
    cfg["run"]["dtype"] = dtype
    cfg["run"]["optimizer"]["multi_precision"] = dtype != "float32"
    return cfg


def cell(dtype="float32", chips=1, batch=8, limits=None):
    """Float32 by default: on the CPU the program and the reference then
    agree to rounding, and the limits can be tight."""
    return {
        "name": "tiny", "chips": chips, "config": config(dtype),
        "traffic": {"runner": "train_step", "batch": batch, "seq": 64,
                    "scan_steps": 3,
                    "mesh": {"shape": [chips], "axes": ["dp"]},
                    "tokens": "uniform", "labels": "uniform"},
        "reference_block_rows": batch // 2,
        "limits": limits or {"loss": 1e-5, "grad": 2e-3, "update": 2e-3}}


PEAK = {"platform": "cpu", "bf16_flops_per_s": 1e12,
        "hbm_bytes_per_s": 1e11}
