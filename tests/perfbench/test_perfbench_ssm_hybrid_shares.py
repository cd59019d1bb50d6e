"""The cut of the hybrid Mamba-2 + attention model adds up to the model: the
eight vocabulary slices of the tied embedding, each a chip's, give side by
side the uncut head's logits, and the slices' loss terms (each slice's sum
of exponentials and the label's logit on the slice that holds it) give the
uncut cross-entropy; the cut's layers are the uncut stack's first ones by
leaf name, in the plain reference and in the zoo's GraniteHybridModel."""

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights
from perfbench.builders import granite_zoo
from perfbench.reference import ssm_hybrid_train as ref

import perfbench_tiny_ssm_hybrid as tiny

SLICES = 8


def _uncut():
    """The tiny configuration with 8 x its vocabulary and twice its
    layers: what the cut is a share of."""
    cfg = tiny.config()
    kinds = cfg["layer_types"]
    return cfg, dict(cfg, vocab_size=SLICES * cfg["vocab_size"],
                     num_hidden_layers=2 * cfg["num_hidden_layers"],
                     layer_types=kinds + kinds)


def test_the_vocabulary_slices_give_the_uncut_heads_logits_and_loss():
    import mxnet_tpu as mx
    cfg, whole = _uncut()
    whole = dict(whole, num_hidden_layers=cfg["num_hidden_layers"])
    w = ref.at_init(weights.make_weights(ref.param_shapes(whole), 11,
                                         "float32"), whole)
    rs = np.random.RandomState(0)
    tokens = rs.randint(0, whole["vocab_size"], (2, 32)).astype(np.int32)
    labels = rs.randint(0, whole["vocab_size"], (2, 32)).astype(np.int32)
    want = ref.logits(w, jnp.asarray(tokens), whole)
    v = cfg["vocab_size"]
    # the hidden states every chip computes alike (the embedding's slices
    # are gathered by id: every chip's rows together are the table)
    h = ref._hidden_states(w, jnp.asarray(tokens), whole,
                           ref.HIGHEST_MATMUL)[-1]
    model = granite_zoo.build_model(whole)
    for name, p in model.collect_params().items():
        leaf = next(k for k in w if granite_zoo._zoo_name(k) == name)
        p.set_data(mx.nd.NDArray._from_data(w[leaf]))
    hn = model.norm(mx.nd.NDArray._from_data(h))
    parts, sums, picked = [], [], np.zeros(labels.shape)
    for s in range(SLICES):
        rows = w["embed"][s * v:(s + 1) * v]
        part = model.lm_head(hn, mx.nd.NDArray._from_data(rows)).asnumpy()
        parts.append(part)
        sums.append(np.exp(part.astype(np.float64)).sum(-1))
        mine = (labels >= s * v) & (labels < (s + 1) * v)
        picked += np.where(mine, np.take_along_axis(
            part, np.clip(labels - s * v, 0, v - 1)[..., None], -1)[..., 0],
            0.0)
    np.testing.assert_allclose(np.concatenate(parts, -1), want, rtol=1e-4,
                               atol=1e-6)
    uncut = jax.nn.logsumexp(want, -1) - jnp.take_along_axis(
        want, jnp.asarray(labels)[..., None], -1)[..., 0]
    np.testing.assert_allclose(np.log(sum(sums)) - picked, uncut, rtol=1e-5)


def test_the_cuts_layers_are_the_uncut_stacks_first_by_leaf_name():
    cfg, whole = _uncut()
    cut, full = ref.param_shapes(cfg), ref.param_shapes(whole)
    layers = cfg["num_hidden_layers"]
    mine = [k for k in cut if k.startswith("layer")]
    assert mine == [k for k in full if k.startswith("layer")
                    and int(k.split(".")[0][5:]) < layers]
    assert all(cut[k] == full[k] for k in mine)
    # what is not a layer differs by the vocabulary's rows alone
    assert set(cut) - set(mine) == {"embed", "final_norm"}
    assert cut["embed"][0] == (cfg["vocab_size"], cfg["hidden_size"])
    assert full["embed"][0] == (whole["vocab_size"], cfg["hidden_size"])
    assert cut["final_norm"] == full["final_norm"]
    names = set(granite_zoo.build_model(cfg).collect_params().keys())
    uncut_names = set(granite_zoo.build_model(whole).collect_params().keys())
    assert names <= uncut_names
    assert uncut_names - names == {
        n for n in uncut_names
        if any(f"layer{i}_" in n for i in range(layers, 2 * layers))}
