"""The hybrid Gated DeltaNet + MoE configuration cut to a size the CPU suite
can run, with the published structure: one period of 3 linear layers and a
full one; 2 key heads and 4 value heads of 16 / 16 in the linear layers,
taps 4; 4 query heads over 2 KV heads of 32 with rotary on 8 dims; 16
routed experts of width 32 (top 3, all held unless a test says otherwise)
and a gated shared expert of width 32; 256 tokens of vocabulary.  The
benchmark's cell keeps the published widths; only the tests use this."""

import copy
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = os.path.join(ROOT, "perfbench", "configs",
                      "qwen3_next_80b_a3b.json")


def config(dtype="float32", held=(0, 16)):
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, num_attention_heads=4, num_key_value_heads=2,
               head_dim=32, linear_num_key_heads=2, linear_num_value_heads=4,
               linear_key_head_dim=16, linear_value_head_dim=16,
               moe_intermediate_size=32, shared_expert_intermediate_size=32,
               num_hidden_layers=4, num_experts=held[1], router_width=16,
               experts_held=list(held), num_experts_per_tok=3,
               vocab_size=256)
    cfg["run"] = copy.deepcopy(cfg["run"])
    cfg["run"]["dtype"] = dtype
    cfg["run"]["optimizer"]["multi_precision"] = dtype != "float32"
    return cfg


def cell(dtype="float32", batch=4, seq=96, limits=None, held=(0, 16)):
    """Float32 by default: on the CPU the program and the reference then
    agree to rounding and the limits can be tight.  96 positions are a
    chunk and a half of the scan: the state is carried, and the last chunk
    is padded."""
    return {
        "name": "tiny_gdn_moe", "chips": 1, "config": config(dtype, held),
        "traffic": {"runner": "train_step", "batch": batch, "seq": seq,
                    "scan_steps": 2,
                    "mesh": {"shape": [1], "axes": ["dp"]},
                    "tokens": "uniform", "labels": "uniform"},
        "reference_block_rows": batch // 2,
        "limits": limits or {"loss": 1e-5, "grad": 2e-4, "update": 2e-4}}
