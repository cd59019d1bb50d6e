"""The MLA + MoE configuration cut to a size the CPU suite can run, with the
published structure: 4 heads of 24 = 16 + 8 and 16-wide values, a latent of
32, 16 routed experts of width 32 (top 3, all held unless a test says
otherwise), 2 shared, one dense and two expert layers, 256 tokens of
vocabulary.  The benchmark's cell keeps the published widths; only the tests
use this."""

import copy
import json
import os

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
CONFIG = os.path.join(ROOT, "perfbench", "configs", "kanana_2_30b_a3b.json")


def config(dtype="float32", held=(0, 16)):
    with open(CONFIG) as f:
        cfg = json.load(f)
    cfg.update(hidden_size=64, num_attention_heads=4, qk_nope_head_dim=16,
               qk_rope_head_dim=8, qk_head_dim=24, v_head_dim=16,
               kv_lora_rank=32, intermediate_size=96,
               moe_intermediate_size=32, num_hidden_layers=3,
               n_routed_experts=held[1], router_width=16,
               experts_held=list(held), num_experts_per_tok=3,
               vocab_size=256)
    cfg["run"] = copy.deepcopy(cfg["run"])
    cfg["run"]["dtype"] = dtype
    cfg["run"]["optimizer"]["multi_precision"] = dtype != "float32"
    return cfg


def cell(dtype="float32", batch=4, seq=32, limits=None, held=(0, 16)):
    """Float32 by default: on the CPU the program and the reference then
    agree to rounding (a sound run reads 4e-7 in ``grad`` and 7e-6 in
    ``update``), and the limits can be tight: at these widths the scores
    are near zero and RoPE left un-interleaved moves ``grad`` by 7e-4."""
    return {
        "name": "tiny_mla_moe", "chips": 1, "config": config(dtype, held),
        "traffic": {"runner": "train_step", "batch": batch, "seq": seq,
                    "scan_steps": 2,
                    "mesh": {"shape": [1], "axes": ["dp"]},
                    "tokens": "uniform", "labels": "uniform"},
        "reference_block_rows": batch // 2,
        "limits": limits or {"loss": 1e-5, "grad": 2e-4, "update": 2e-4}}
