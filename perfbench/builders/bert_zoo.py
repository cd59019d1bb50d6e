"""The system under test for the BERT training cells: the zoo's BERTModel,
Adam multi_precision and parallel.TrainStep, built as chip_smoke.py and
bench.py build them, but with the benchmark's own seeded weights put in and
the net placed on the step's device, so that no deferred-init forward runs on
the host.

This is the one file that knows the program's names.  It reads the
optimizer's state through ``TrainStep._trainable`` / ``._states``: the
program offers no public accessor (PERF.md, Open questions).
"""

import contextlib

import numpy as np

# the reference's leaf name -> the zoo parameter's suffix
_TOP = {"word_embed": "word_weight", "pos_embed": "position_weight",
        "embed_ln_g": "embln_gamma", "embed_ln_b": "embln_beta",
        "pooler_w": "pooler_weight", "pooler_b": "pooler_bias",
        "decoder_w": "decoder_weight", "decoder_b": "decoder_bias"}
_LAYER = {"qkv_w": "attn_qkv_weight", "qkv_b": "attn_qkv_bias",
          "proj_w": "attn_proj_weight", "proj_b": "attn_proj_bias",
          "ln1_g": "ln1_gamma", "ln1_b": "ln1_beta",
          "ffn1_w": "ffn1_weight", "ffn1_b": "ffn1_bias",
          "ffn2_w": "ffn2_weight", "ffn2_b": "ffn2_bias",
          "ln2_g": "ln2_gamma", "ln2_b": "ln2_beta"}
_PREFIX = "bert_"


def _zoo_name(leaf):
    if leaf in _TOP:
        return _PREFIX + _TOP[leaf]
    layer, part = leaf.split(".")
    return f"{_PREFIX}enc_{layer}_{_LAYER[part]}"


class Program:
    """One compiled TrainStep with its state: what set-up warms up is what
    the window drives."""

    def __init__(self, cfg, traffic, weights, devices):
        import jax
        import mxnet_tpu as mx
        from mxnet_tpu import parallel
        from mxnet_tpu.gluon.model_zoo import bert
        self._mx = mx
        run = cfg["run"]
        self._bf16 = run["dtype"] == "bfloat16"
        ctx = mx.tpu() if devices[0].platform == "tpu" else mx.cpu()
        model = bert.BERTModel(
            vocab_size=cfg["vocab_size"],
            num_layers=cfg["num_hidden_layers"], units=cfg["hidden_size"],
            hidden_size=cfg["intermediate_size"],
            num_heads=cfg["num_attention_heads"],
            max_length=cfg["max_position_embeddings"],
            dropout=cfg["hidden_dropout_prob"], prefix=_PREFIX)
        if self._bf16:
            import ml_dtypes
            model.cast(ml_dtypes.bfloat16)
        params = model.collect_params()
        self._leaf_of = {_zoo_name(leaf): leaf for leaf in weights}
        if set(self._leaf_of) != set(params.keys()):
            raise RuntimeError(
                "the zoo model and the reference disagree on the leaves: "
                f"{sorted(set(self._leaf_of) ^ set(params.keys()))[:6]}")
        for name, p in params.items():
            p.reset_ctx(ctx)
            p.set_data(mx.nd.NDArray._from_data(
                weights[self._leaf_of[name]], ctx=ctx))

        def loss_fn(out, labels):
            _, _, logits = out
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

    @contextlib.contextmanager
    def _precision(self):
        """XLA's default matmul precision around the bf16 step (the package
        default is 'highest', for float32 parity), as bench.py and
        chip_smoke.py scope it."""
        import jax
        if self._bf16:
            with jax.default_matmul_precision("default"):
                yield
        else:
            yield

    def run(self, tokens, labels):
        """Enqueue one dispatch of ``tokens.shape[0]`` scanned steps; the
        handle's losses are fetched by ``losses``."""
        nd = self._mx.nd
        with self._precision():
            return self.step.run(nd.array(tokens), nd.array(labels))

    @staticmethod
    def losses(handle):
        return np.asarray(handle.asnumpy(), np.float64)

    def state(self):
        """(master, m) as the optimizer holds them, by the reference's leaf
        names: the float32 parameters and Adam's first moment."""
        master, m = {}, {}
        for i, p in enumerate(self.step._trainable):
            leaf = self._leaf_of[p.name]
            st = self.step._states[i]
            if isinstance(st[1], (tuple, list)):    # (master, (m, v))
                master[leaf], m[leaf] = st[0]._data, st[1][0]._data
            else:                                   # float32 weights: (m, v)
                master[leaf], m[leaf] = p.data()._data, st[0]._data
        return master, m

    def close(self):
        self.step = self.model = None
