"""The system under test for the looped language model's training cells:
the zoo's OuroModel (gluon/model_zoo/ouro.py) with its expected exit loss,
Adam multi_precision and parallel.TrainStep, built as
perfbench/builders/qwen3_next_zoo.py builds its model: the benchmark's own
seeded weights put in by name, the net placed on the step's device, the
step's parameter order fixed from shapes.  What drives the step, reads its
state and frees it is mla_moe_zoo.Program's.

This file knows the program's names: the reference's leaves against the
zoo's parameters.
"""

import numpy as np

# imported here, not where the model is built: a tree without the model
# fails on this cell before it makes a single weight
from mxnet_tpu.gluon.model_zoo import ouro

from perfbench.builders import mla_moe_zoo

_PREFIX = "ouro_"
# the reference's leaf name (less "layer<n>.") -> the zoo parameter's suffix
_TOP = {"embed": "tok_weight", "final_norm": "final_norm_weight",
        "exit_w": "exit_gate_weight", "exit_b": "exit_gate_bias",
        "lm_head_w": "lm_head_weight"}
_LAYER = {"attn_in_norm": "attn_in_norm_weight", "q_w": "attn_q_weight",
          "k_w": "attn_k_weight", "v_w": "attn_v_weight",
          "o_w": "attn_o_weight", "attn_out_norm": "attn_out_norm_weight",
          "mlp_in_norm": "mlp_in_norm_weight", "gate_w": "mlp_gate_weight",
          "up_w": "mlp_up_weight", "down_w": "mlp_down_weight",
          "mlp_out_norm": "mlp_out_norm_weight"}


def _zoo_name(leaf):
    if leaf in _TOP:
        return _PREFIX + _TOP[leaf]
    layer, part = leaf.split(".")
    return f"{_PREFIX}{layer}_{_LAYER[part]}"


def build_model(cfg):
    """The zoo model of a configuration file (published keys)."""
    if cfg["num_key_value_heads"] != cfg["num_attention_heads"]:
        raise ValueError("OuroModel has no grouped heads")
    return ouro.OuroModel(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        units=cfg["hidden_size"], hidden=cfg["intermediate_size"],
        heads=cfg["num_attention_heads"], head_dim=cfg["head_dim"],
        loop_steps=cfg["total_ut_steps"], eps=cfg["rms_norm_eps"],
        rope_base=cfg["rope_theta"], prefix=_PREFIX)


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
        self._given = {}        # no leaf starts off the harness's own draw
        for name, p in params.items():
            p.reset_ctx(ctx)
            p.set_data(mx.nd.NDArray._from_data(
                weights[self._leaf_of[name]], ctx=ctx))
        beta = cfg["exit_entropy_beta"]

        def loss_fn(out, labels):
            return ouro.expected_exit_loss(out, labels, beta)

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
        # qwen3_next_zoo does (`run` would else finish deferred init with
        # an imperative forward, one program an op at 4,096 positions)
        self.step._resolve(None)
