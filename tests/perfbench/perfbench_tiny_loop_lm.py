"""The looped language model's configuration cut to a size the CPU suite can
run, with the published structure: 2 layers applied 4 times, 2 heads of 16
(as many key-value heads), a SwiGLU of width 48, sandwich norms, the exit
gate, 64 tokens of vocabulary.  The benchmark's cell keeps the published
widths; only the tests use this."""

import copy
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = os.path.join(ROOT, "perfbench", "configs", "ouro_2_6b.json")


def config(dtype="float32"):
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=32, intermediate_size=48, num_attention_heads=2,
               num_key_value_heads=2, head_dim=16, num_hidden_layers=2,
               vocab_size=64)
    cfg["run"] = copy.deepcopy(cfg["run"])
    cfg["run"]["dtype"] = dtype
    cfg["run"]["optimizer"]["multi_precision"] = dtype != "float32"
    return cfg


def cell(dtype="float32", batch=2, seq=32, limits=None):
    """Float32 by default: on the CPU the program and the reference then
    agree to rounding and the limits can be tight.  The reference takes
    the loss a row at a time: two blocks."""
    return {
        "name": "tiny_loop_lm", "chips": 1, "config": config(dtype),
        "traffic": {"runner": "train_step", "batch": batch, "seq": seq,
                    "scan_steps": 2,
                    "mesh": {"shape": [1], "axes": ["dp"]},
                    "tokens": "uniform", "labels": "uniform"},
        "reference_block_rows": batch // 2,
        "limits": limits or {"loss": 1e-5, "grad": 2e-4, "update": 2e-4}}
