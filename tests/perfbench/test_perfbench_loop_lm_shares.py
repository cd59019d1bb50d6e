"""The cut of the looped language model adds up to the model: the eight
vocabulary slices' logits side by side are the uncut head's, a slice's
loss is taken over the slice, and the cut's layers are the uncut stack's
first ones by leaf name, in the plain reference and in the zoo's
OuroModel."""

import jax.numpy as jnp
import numpy as np

from perfbench import weights
from perfbench.builders import ouro_zoo
from perfbench.reference import loop_lm_train as ref

import perfbench_tiny_loop_lm as tiny

SLICES = 8


def _uncut():
    """The tiny configuration with 8 x its vocabulary and twice its
    layers: what the cut is a share of."""
    cfg = tiny.config()
    return cfg, dict(cfg, vocab_size=SLICES * cfg["vocab_size"],
                     num_hidden_layers=2 * cfg["num_hidden_layers"])


def test_the_vocabulary_slices_side_by_side_are_the_uncut_head():
    import mxnet_tpu as mx
    cfg, whole = _uncut()
    whole = dict(whole, num_hidden_layers=cfg["num_hidden_layers"])
    w = weights.make_weights(ref.param_shapes(whole), 11, "float32")
    tokens = np.random.RandomState(0).randint(
        0, whole["vocab_size"], (2, 32)).astype(np.int32)
    hs = ref.exits(w, jnp.asarray(tokens), whole)
    want = jnp.einsum("tbsu,vu->tbsv", hs, w["lm_head_w"],
                      precision="highest")
    v = cfg["vocab_size"]
    model = ouro_zoo.build_model(whole)
    for name, p in model.collect_params().items():
        leaf = next(k for k in w if ouro_zoo._zoo_name(k) == name)
        p.set_data(mx.nd.NDArray._from_data(w[leaf]))
    total = None
    parts = []
    for s in range(SLICES):
        # a chip's slice of the head over the same hidden states (the
        # embedding's slices are gathered by id: every chip's rows
        # together are the table)
        head = w["lm_head_w"][s * v:(s + 1) * v]
        parts.append(jnp.einsum("tbsu,vu->tbsv", hs, head,
                                precision="highest"))
        model.lm_head.weight.set_data(mx.nd.NDArray._from_data(
            jnp.zeros_like(w["lm_head_w"]).at[s * v:(s + 1) * v].set(head)))
        logits, _p = model(mx.nd.array(tokens, dtype="int32"))
        got = logits.asnumpy()
        np.testing.assert_allclose(got[..., s * v:(s + 1) * v], parts[-1],
                                   rtol=1e-4, atol=1e-6)
        total = got if total is None else total + got
    np.testing.assert_allclose(jnp.concatenate(parts, -1), want, rtol=1e-6)
    np.testing.assert_allclose(total, want, rtol=1e-4, atol=1e-6)


def test_the_cuts_layers_are_the_uncut_stacks_first_by_leaf_name():
    cfg, whole = _uncut()
    cut, full = ref.param_shapes(cfg), ref.param_shapes(whole)
    layers = cfg["num_hidden_layers"]
    mine = [k for k in cut if k.startswith("layer")]
    assert mine == [k for k in full if k.startswith("layer")
                    and int(k.split(".")[0][5:]) < layers]
    assert all(cut[k] == full[k] for k in mine)
    # what is not a layer differs by the vocabulary's rows alone
    for k in set(cut) - set(mine):
        assert cut[k][0][1:] == full[k][0][1:], k
        assert (cut[k][0][0] != full[k][0][0]) == (k in ("embed",
                                                         "lm_head_w")), k
    # and the zoo names them alike: the stage's parameters are the first
    # layers' of the uncut model
    names = set(ouro_zoo.build_model(cfg).collect_params().keys())
    uncut_names = set(ouro_zoo.build_model(whole).collect_params().keys())
    assert names <= uncut_names
    assert {n for n in uncut_names - names} == {
        n for n in uncut_names
        if any(f"layer{i}_" in n for i in range(layers, 2 * layers))}


def test_a_stage_of_the_stack_is_the_uncut_stacks_first_layers():
    """One pass over the cut's layers equals the uncut stack stopped after
    as many layers, on the same leaves."""
    cfg, whole = _uncut()
    w = weights.make_weights(ref.param_shapes(whole), 12, "float32")
    one_pass = dict(cfg, total_ut_steps=1, vocab_size=whole["vocab_size"])
    tokens = jnp.asarray(np.random.RandomState(1).randint(
        0, whole["vocab_size"], (2, 32)).astype(np.int32))
    got = ref.exits({k: w[k] for k in ref.param_shapes(one_pass)}, tokens,
                    one_pass)
    x = w["embed"][tokens]
    for n in range(cfg["num_hidden_layers"]):
        x = ref._layer(x, {leaf: w[f"layer{n}.{leaf}"]
                           for leaf in ref._LAYER_LEAVES}, whole,
                       ref.HIGHEST_MATMUL)
    want = ref._norm(x, w["final_norm"], whole["rms_norm_eps"])
    np.testing.assert_allclose(got[0], want, rtol=1e-5, atol=1e-6)
