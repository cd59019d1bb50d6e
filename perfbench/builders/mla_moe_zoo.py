"""The system under test for the MLA + MoE training cells: the zoo's
MLAMoEModel (gluon/model_zoo/mla_moe.py), Adam multi_precision and
parallel.TrainStep, built as perfbench/builders/bert_zoo.py builds BERT's:
the benchmark's own seeded weights put in, the net placed on the step's
device.  What drives the step and reads its state is bert_zoo.Program's.

This file knows the program's names: the reference's leaves against the
zoo's parameters.
"""

import numpy as np

# imported here, not where the model is built: a tree without the model
# fails on this cell before it makes a single weight
from mxnet_tpu.gluon.model_zoo import mla_moe

from perfbench.builders import bert_zoo

_PREFIX = "mlamoe_"
# the reference's leaf name (less "layer<n>.") -> the zoo parameter's suffix
_TOP = {"embed": "tok_weight", "final_norm": "final_norm_weight",
        "lm_head_w": "lm_head_weight"}
_LAYER = {"attn_norm": "attn_norm_weight", "ffn_norm": "ffn_norm_weight",
          "q_w": "mla_q_weight", "kv_a_w": "mla_kv_a_weight",
          "kv_a_norm": "mla_kv_a_norm_weight", "kv_b_w": "mla_kv_b_weight",
          "o_w": "mla_o_weight",
          "mlp_gate_w": "mlp_gate_weight", "mlp_up_w": "mlp_up_weight",
          "mlp_down_w": "mlp_down_weight",
          "router_w": "moe_router_weight", "router_b": "moe_router_bias",
          "experts_gate": "moe_experts_gate", "experts_up": "moe_experts_up",
          "experts_down": "moe_experts_down",
          "shared_gate_w": "moe_shared_gate_weight",
          "shared_up_w": "moe_shared_up_weight",
          "shared_down_w": "moe_shared_down_weight"}


def _zoo_name(leaf):
    if leaf in _TOP:
        return _PREFIX + _TOP[leaf]
    layer, part = leaf.split(".")
    return f"{_PREFIX}{layer}_{_LAYER[part]}"


def build_model(cfg):
    """The zoo model of a configuration file (published keys)."""
    return mla_moe.MLAMoEModel(
        vocab_size=cfg["vocab_size"], num_layers=cfg["num_hidden_layers"],
        units=cfg["hidden_size"], dense_hidden=cfg["intermediate_size"],
        first_dense=cfg["first_k_dense_replace"], eps=cfg["rms_norm_eps"],
        attention=dict(
            heads=cfg["num_attention_heads"],
            qk_nope=cfg["qk_nope_head_dim"], qk_rope=cfg["qk_rope_head_dim"],
            v_head=cfg["v_head_dim"], kv_lora_rank=cfg["kv_lora_rank"],
            rope_base=cfg["rope_theta"],
            rope_interleave=cfg["rope_interleave"]),
        moe=dict(
            hidden_size=cfg["moe_intermediate_size"],
            num_experts=cfg["router_width"],
            num_experts_per_token=cfg["num_experts_per_tok"],
            experts_held=tuple(cfg["experts_held"]),
            num_shared_experts=cfg["n_shared_experts"],
            routed_scaling_factor=cfg["routed_scaling_factor"],
            norm_topk_prob=cfg["norm_topk_prob"]),
        prefix=_PREFIX)


class Program(bert_zoo.Program):
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
        # the harness draws every 'normal' leaf at one width; the leaves
        # the configuration's init_scale names start at their factor of it
        # (powers of two: exact in bfloat16), and `state` needs what was
        # given to report their change the way the harness takes it
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

    def state(self):
        """(master, m) as `bert_zoo.Program.state` gives them, the scaled
        leaves moved to where the harness's own values stand: it takes the
        parameters' change as ``master - given``, and for a leaf that
        started at ``scale * given`` that is then the change it made."""
        master, m = super().state()
        for leaf, (given, scale) in self._given.items():
            master[leaf] = master[leaf] \
                + given.astype(master[leaf].dtype) * (1.0 - scale)
        return master, m

    def close(self):
        """Free the step's arrays themselves, not only this object's hold
        on them: the float32 reference needs the chip's memory next, and
        whatever else still points at the model (a cached trace's closure)
        would keep 2.1 GB of bfloat16 weights and gradient buffers on it."""
        held = list(self.step._state_nds or ())
        for p in self.model.collect_params().values():
            data = p.data()
            held += [data] + ([data._grad] if data._grad is not None else [])
        for nd in held:
            nd._data.delete()
        self._given = {}
        super().close()
