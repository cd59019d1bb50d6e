"""The system under test for the hybrid Gated DeltaNet + MoE training cells:
the zoo's Qwen3NextModel (gluon/model_zoo/qwen3_next.py), Adam
multi_precision and parallel.TrainStep, built as
perfbench/builders/mla_moe_zoo.py builds its model: the benchmark's own
seeded weights put in (the leaves the configuration's ``init_scale`` names at
their factor), the net placed on the step's device.  What drives the step,
reads its state and frees it is mla_moe_zoo.Program's.

This file knows the program's names: the reference's leaves against the
zoo's parameters.
"""

import numpy as np

# imported here, not where the model is built: a tree without the model
# fails on this cell before it makes a single weight
from mxnet_tpu.gluon.model_zoo import qwen3_next

from perfbench.builders import mla_moe_zoo

_PREFIX = "qwen3next_"
# the reference's leaf name (less "layer<n>.") -> the zoo parameter's suffix
_TOP = {"embed": "tok_weight", "final_norm": "final_norm_weight",
        "lm_head_w": "lm_head_weight"}
_LAYER = {"attn_norm": "attn_norm_weight", "ffn_norm": "ffn_norm_weight",
          "in_qkvz_w": "gdn_in_qkvz_weight", "in_ba_w": "gdn_in_ba_weight",
          "conv_w": "gdn_conv_weight", "A_log": "gdn_scan_A_log",
          "dt_bias": "gdn_scan_dt_bias",
          "gate_norm": "gdn_gate_norm_weight", "out_w": "gdn_out_weight",
          "q_w": "attn_q_weight", "k_w": "attn_k_weight",
          "v_w": "attn_v_weight", "q_norm": "attn_q_norm_weight",
          "k_norm": "attn_k_norm_weight", "o_w": "attn_o_weight",
          "router_w": "moe_router_weight",
          "experts_gate": "moe_experts_gate", "experts_up": "moe_experts_up",
          "experts_down": "moe_experts_down",
          "shared_gate_w": "moe_shared_gate_weight",
          "shared_up_w": "moe_shared_up_weight",
          "shared_down_w": "moe_shared_down_weight",
          "shgate_w": "moe_shgate_weight"}


def _zoo_name(leaf):
    if leaf in _TOP:
        return _PREFIX + _TOP[leaf]
    layer, part = leaf.split(".")
    return f"{_PREFIX}{layer}_{_LAYER[part]}"


def build_model(cfg):
    """The zoo model of a configuration file (published keys)."""
    head_dim = cfg["head_dim"]
    return qwen3_next.Qwen3NextModel(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        units=cfg["hidden_size"],
        full_attention_interval=cfg["full_attention_interval"],
        eps=cfg["rms_norm_eps"],
        attention=dict(
            heads=cfg["num_attention_heads"],
            kv_heads=cfg["num_key_value_heads"], head_dim=head_dim,
            rotary_dim=int(head_dim * cfg["partial_rotary_factor"]),
            rope_base=cfg["rope_theta"]),
        linear=dict(
            key_heads=cfg["linear_num_key_heads"],
            value_heads=cfg["linear_num_value_heads"],
            key_dim=cfg["linear_key_head_dim"],
            value_dim=cfg["linear_value_head_dim"],
            conv_taps=cfg["linear_conv_kernel_dim"]),
        moe=dict(
            hidden_size=cfg["moe_intermediate_size"],
            num_experts=cfg["router_width"],
            num_experts_per_token=cfg["num_experts_per_tok"],
            experts_held=tuple(cfg["experts_held"]),
            num_shared_experts=cfg["shared_expert_intermediate_size"]
            // cfg["moe_intermediate_size"],
            norm_topk_prob=cfg["norm_topk_prob"]),
        prefix=_PREFIX)


class Program(mla_moe_zoo.Program):
    """One compiled TrainStep with its state: what set-up warms up is what
    the window drives."""

    def __init__(self, cfg, traffic, weights, devices):
        import mxnet_tpu as mx
        from mxnet_tpu import parallel
        self._mx = mx
        run = cfg["run"]
        self._bf16 = run["dtype"] == "bfloat16"
        ctx = mx.tpu() if devices[0].platform == "tpu" else mx.cpu()
        model = build_model(cfg)
        if self._bf16:
            import ml_dtypes
            model.cast(ml_dtypes.bfloat16)
        params = model.collect_params()
        self._leaf_of = {_zoo_name(leaf): leaf for leaf in weights}
        if set(self._leaf_of) != set(params.keys()):
            raise RuntimeError(
                "the zoo model and the reference disagree on the leaves: "
                f"{sorted(set(self._leaf_of) ^ set(params.keys()))[:6]}")
        # as mla_moe_zoo.Program: the harness draws every leaf at one
        # width; `state` needs what was given to report a scaled leaf's
        # change the way the harness takes it
        scale = cfg.get("init_scale", {})
        self._given = {leaf: (w, scale[leaf.rsplit(".", 1)[-1]])
                       for leaf, w in weights.items()
                       if leaf.rsplit(".", 1)[-1] in scale}
        for name, p in params.items():
            p.reset_ctx(ctx)
            leaf = self._leaf_of[name]
            w = weights[leaf]
            if leaf in self._given:
                w = (w * self._given[leaf][1]).astype(w.dtype)
            p.set_data(mx.nd.NDArray._from_data(w, ctx=ctx))

        def loss_fn(logits, labels):
            return mx.nd.softmax_cross_entropy(
                logits.reshape((-1, logits.shape[-1])).astype("float32"),
                labels.reshape((-1,))) / labels.size

        o = run["optimizer"]
        opt = mx.optimizer.Adam(
            learning_rate=o["learning_rate"], beta1=o["beta1"],
            beta2=o["beta2"], epsilon=o["epsilon"],
            multi_precision=o["multi_precision"])
        mesh = parallel.make_mesh(
            shape=tuple(traffic["mesh"]["shape"]),
            axis_names=tuple(traffic["mesh"]["axes"]),
            devices=list(devices[:int(np.prod(traffic["mesh"]["shape"]))]))
        self.model = model
        self.step = parallel.TrainStep(model, loss_fn, opt, mesh=mesh)
        # every leaf was set at its full shape above, so nothing is
        # deferred: fix the step's parameter order from shapes alone, as
        # `TrainStep.lowered` does.  `run` would else finish deferred init
        # with an imperative forward over the first row, one program an op
        # at 8,192 positions, most of them compiled too fast to be cached
        self.step._resolve(None)
