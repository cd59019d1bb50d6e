"""The system under test for the short-convolution + attention MoE training
cells: the zoo's Lfm2MoeModel (gluon/model_zoo/lfm2_moe.py), Adam
multi_precision and parallel.TrainStep, built as
perfbench/builders/granite_zoo.py builds its model: the benchmark's own
seeded weights put in (the leaves the configuration's ``init_scale`` names
at their factor), the net placed on the step's device, the step's parameter
order fixed from shapes.  What drives the step, reads its state and frees
it is mla_moe_zoo.Program's.

This file knows the program's names: the reference's leaves against the
zoo's parameters.
"""

import types

# imported here, not where the model is built: a tree without the model
# fails on this cell before it makes a single weight
from mxnet_tpu.gluon.model_zoo import lfm2_moe

from perfbench.builders import granite_zoo, mla_moe_zoo

_PREFIX = "lfm2_"
# the reference's leaf name (less "layer<n>.") -> the zoo parameter's suffix
_TOP = {"embed": "embed_weight", "final_norm": "norm_weight"}
_LAYER = {"operator_norm": "operator_norm_weight",
          "ffn_norm": "ffn_norm_weight",
          "in_proj_w": "conv_in_proj_weight",
          "conv_w": "conv_gated_conv_weight",
          "out_w": "conv_out_proj_weight",
          "q_w": "attn_q_proj_weight", "k_w": "attn_k_proj_weight",
          "v_w": "attn_v_proj_weight", "q_norm": "attn_q_norm_weight",
          "k_norm": "attn_k_norm_weight", "o_w": "attn_o_proj_weight",
          "ffn_gate_w": "ffn_gate_weight", "ffn_up_w": "ffn_up_weight",
          "ffn_down_w": "ffn_down_weight",
          "router_w": "moe_router_weight", "router_b": "moe_router_bias",
          "experts_gate": "moe_experts_gate", "experts_up": "moe_experts_up",
          "experts_down": "moe_experts_down"}


def _zoo_name(leaf):
    if leaf in _TOP:
        return _PREFIX + _TOP[leaf]
    layer, part = leaf.split(".")
    return f"{_PREFIX}{layer}_{_LAYER[part]}"


def build_model(cfg):
    """The zoo model of a configuration file (published keys)."""
    heads = cfg["num_attention_heads"]
    return lfm2_moe.Lfm2MoeModel(
        vocab_size=cfg["vocab_size"],
        layer_types=[cfg["layer_types"][i] for i in cfg["layers_held"]],
        units=cfg["hidden_size"], dense_hidden=cfg["intermediate_size"],
        num_dense=cfg["first_k_dense_replace"],
        conv=dict(conv_taps=cfg["conv_L_cache"]),
        attention=dict(heads=heads, kv_heads=cfg["num_key_value_heads"],
                       head_dim=cfg["hidden_size"] // heads,
                       rope_base=cfg["rope_parameters"]["rope_theta"]),
        moe=dict(hidden_size=cfg["moe_intermediate_size"],
                 num_experts=cfg["router_width"],
                 num_experts_per_token=cfg["num_experts_per_tok"],
                 experts_held=tuple(cfg["experts_held"]),
                 routed_scaling_factor=cfg["routed_scaling_factor"],
                 norm_topk_prob=cfg["norm_topk_prob"],
                 router_eps=cfg["router_norm_eps"]),
        eps=cfg["norm_eps"], prefix=_PREFIX)


class Program(mla_moe_zoo.Program):
    """One compiled TrainStep with its state: what set-up warms up is what
    the window drives."""

    # granite_zoo.Program's constructor, run with this file's model and
    # names: the accepted builders' constructors differ in those two names
    # alone (PERF.md section 7), so it is reused, not copied.  The two are
    # looked up here when it runs, as its own module's would be
    __init__ = types.FunctionType(
        granite_zoo.Program.__init__.__code__,
        dict(vars(granite_zoo), build_model=lambda cfg: build_model(cfg),
             _zoo_name=lambda leaf: _zoo_name(leaf)), "__init__")
