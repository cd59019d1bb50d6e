"""The system under test for the hybrid Mamba-2 + attention training cells:
the zoo's GraniteHybridModel (gluon/model_zoo/granite_hybrid.py), Adam
multi_precision and parallel.TrainStep, built as
perfbench/builders/qwen3_next_zoo.py builds its model: the benchmark's own
seeded weights put in (the leaves the configuration's ``init_scale`` names
at their factor), the net placed on the step's device, the step's parameter
order fixed from shapes.  What drives the step, reads its state and frees
it is mla_moe_zoo.Program's.

This file knows the program's names: the reference's leaves against the
zoo's parameters.
"""

import numpy as np

# imported here, not where the model is built: a tree without the model
# fails on this cell before it makes a single weight
from mxnet_tpu.gluon.model_zoo import granite_hybrid

from perfbench.builders import mla_moe_zoo

_PREFIX = "granite_"
# the reference's leaf name (less "layer<n>.") -> the zoo parameter's suffix
_TOP = {"embed": "embed_weight", "final_norm": "final_norm_weight"}
_LAYER = {"in_norm": "in_norm_weight", "post_norm": "post_norm_weight",
          "in_proj_w": "mamba_in_proj_weight", "conv_w": "mamba_conv_weight",
          "conv_b": "mamba_conv_bias", "A_log": "mamba_scan_A_log",
          "D": "mamba_scan_D", "dt_bias": "mamba_scan_dt_bias",
          "gate_norm": "mamba_norm_weight", "out_w": "mamba_out_proj_weight",
          "q_w": "attn_q_proj_weight", "k_w": "attn_k_proj_weight",
          "v_w": "attn_v_proj_weight", "o_w": "attn_o_proj_weight",
          "mlp_in_w": "mlp_input_linear_weight",
          "mlp_out_w": "mlp_output_linear_weight"}


def _zoo_name(leaf):
    if leaf in _TOP:
        return _PREFIX + _TOP[leaf]
    layer, part = leaf.split(".")
    return f"{_PREFIX}{layer}_{_LAYER[part]}"


def build_model(cfg):
    """The zoo model of a configuration file (published keys)."""
    if cfg["num_local_experts"]:
        raise ValueError("GraniteHybridModel has no routed experts")
    heads = cfg["num_attention_heads"]
    return granite_hybrid.GraniteHybridModel(
        vocab_size=cfg["vocab_size"],
        layer_types=cfg["layer_types"][:cfg["num_hidden_layers"]],
        units=cfg["hidden_size"], hidden=cfg["shared_intermediate_size"],
        mamba=dict(heads=cfg["mamba_n_heads"], head_dim=cfg["mamba_d_head"],
                   groups=cfg["mamba_n_groups"], state=cfg["mamba_d_state"],
                   conv_taps=cfg["mamba_d_conv"],
                   chunk=cfg["mamba_chunk_size"]),
        attention=dict(heads=heads, kv_heads=cfg["num_key_value_heads"],
                       head_dim=cfg["hidden_size"] // heads,
                       multiplier=cfg["attention_multiplier"]),
        embedding_multiplier=cfg["embedding_multiplier"],
        residual_multiplier=cfg["residual_multiplier"],
        logits_scaling=cfg["logits_scaling"], eps=cfg["rms_norm_eps"],
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
        # the other decoder builders do (`run` would else finish deferred
        # init with an imperative forward, one program an op at 4,096
        # positions)
        self.step._resolve(None)
