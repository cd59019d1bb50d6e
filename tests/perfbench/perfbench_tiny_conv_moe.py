"""The short-convolution + attention MoE configuration cut to a size the
CPU suite can run, with the published structure: three layers (a dense
short convolution, attention over the MoE, a short convolution over the
MoE: the model's layers 0, 2 and 3), 3 taps, 4 query heads over 2
key-value heads of 8 with their norms and RoPE, a dense SwiGLU of 48, 16
routed experts of 16 (top 4, all held unless a test says otherwise), 64
tokens of tied vocabulary.  The benchmark's cell keeps the published
widths; only the tests use this."""

import copy
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = os.path.join(ROOT, "perfbench", "configs", "lfm2_24b_a2b.json")


def config(dtype="float32", held=(0, 16)):
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=32, intermediate_size=48, moe_intermediate_size=16,
               num_attention_heads=4, num_key_value_heads=2,
               num_hidden_layers=3, layers_held=[0, 2, 3],
               num_experts=held[1], router_width=16, experts_held=list(held),
               vocab_size=64)
    cfg["run"] = copy.deepcopy(cfg["run"])
    cfg["run"]["dtype"] = dtype
    cfg["run"]["optimizer"]["multi_precision"] = dtype != "float32"
    return cfg


def cell(dtype="float32", batch=2, seq=32, limits=None, held=(0, 16)):
    """Float32 by default: on the CPU the program and the reference then
    agree to rounding and the limits can be tight.  The reference takes
    the loss a row at a time: two blocks."""
    return {
        "name": "tiny_conv_moe", "chips": 1, "config": config(dtype, held),
        "traffic": {"runner": "train_step", "batch": batch, "seq": seq,
                    "scan_steps": 2,
                    "mesh": {"shape": [1], "axes": ["dp"]},
                    "tokens": "uniform", "labels": "uniform"},
        "reference_block_rows": batch // 2,
        "limits": limits or {"loss": 1e-5, "grad": 2e-4, "update": 2e-4}}
