"""The cut of the short-convolution + attention MoE model adds up to the
model: two chips' shares of a layer's experts give, summed, the uncut
layer's routed part in the plain reference; the eight vocabulary slices of
the tied embedding, each a chip's, give side by side the uncut head's
logits, and the slices' loss terms the uncut cross-entropy.  (The same
share of the zoo's layer: tests/test_lfm2_moe.py.)"""

import jax
import jax.numpy as jnp
import numpy as np

from perfbench import weights
from perfbench.builders import lfm2_zoo
from perfbench.reference import conv_moe_train as ref

import perfbench_tiny_conv_moe as tiny

SLICES = 8


def test_two_shares_of_the_experts_add_up_to_the_uncut_layer():
    cfg = tiny.config(held=(0, 16))
    shapes = ref.param_shapes(cfg)
    w = ref.at_init(weights.make_weights(shapes, 5, "float32"), cfg)
    p = ref._layer_leaves(w, 1)                 # the attention + MoE layer
    x = jax.random.normal(jax.random.PRNGKey(3), (64, cfg["hidden_size"]))
    whole = ref.moe_ffn(x, p, cfg, ref.HIGHEST_MATMUL)
    parts = []
    for first in (0, 8):
        mine = dict(p, **{k: p[k][first:first + 8] for k in
                          ("experts_gate", "experts_up", "experts_down")})
        parts.append(ref.moe_ffn(x, mine, cfg, ref.HIGHEST_MATMUL,
                                 held=(first, 8)))
    np.testing.assert_allclose(parts[0] + parts[1], whole, rtol=1e-5,
                               atol=1e-7)
    # each share is a real part: both carry weight
    assert float(jnp.abs(parts[0]).sum()) > 0.1 * float(jnp.abs(whole).sum())
    assert float(jnp.abs(parts[1]).sum()) > 0.1 * float(jnp.abs(whole).sum())


def test_the_vocabulary_slices_give_the_uncut_heads_logits_and_loss():
    import mxnet_tpu as mx
    cfg = tiny.config()
    whole = dict(cfg, vocab_size=SLICES * cfg["vocab_size"])
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
    model = lfm2_zoo.build_model(whole)
    for name, p in model.collect_params().items():
        leaf = next(k for k in w if lfm2_zoo._zoo_name(k) == name)
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
