"""The short-convolution + attention MoE decoder of the zoo
(gluon/model_zoo/lfm2_moe.py): a layer of each kind against its equations
written out in NumPy (B, C and x by their roles, the taps' causal shift, the
q/k norms and RoPE), the router's choice-only bias and its 1e-6, the tied
weight's gradient as the sum of its two uses, two shares of a layer's
experts against the uncut layer, the rule that makes every layer again, the
names its blocks put into the compiled step and the counters it keeps; the
model against the plain reference on seeded weights."""

import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import mxnet_tpu as mx
from mxnet_tpu import autograd, parallel, telemetry
from mxnet_tpu.gluon.model_zoo import lfm2_moe

VOCAB, UNITS, DENSE, EXPERT = 64, 32, 48, 16
CONV = dict(conv_taps=3)
ATTN = dict(heads=4, kv_heads=2, head_dim=8, rope_base=1e6)
MOE = dict(hidden_size=EXPERT, num_experts=16, num_experts_per_token=4,
           experts_held=(0, 16), routed_scaling_factor=1.0,
           norm_topk_prob=True, router_eps=1e-6)
KINDS = ["conv", "full_attention", "conv"]
EPS = 1e-5


def _net(seed=0, kinds=KINDS, held=(0, 16)):
    net = lfm2_moe.Lfm2MoeModel(
        VOCAB, kinds, UNITS, DENSE, 1, CONV, ATTN,
        dict(MOE, experts_held=held), eps=EPS, prefix="lfm2_")
    net.initialize(mx.init.Normal(0.02))
    rs = np.random.RandomState(seed)
    for name, p in net.collect_params().items():
        # every leaf off its default, so that nothing a test holds is
        # hidden by a 1 or a 0
        if name.endswith("norm_weight"):
            value = 1 + 0.1 * rs.randn(*p.shape)
        elif name.endswith("gated_conv_weight"):
            value = 0.5 * rs.randn(*p.shape)
        else:
            value = 0.2 * rs.randn(*p.shape)
        p.set_data(mx.nd.array(value))
    return net


def _tokens(steps=2, batch=2, seq=20, seed=0):
    return np.random.RandomState(seed).randint(
        0, VOCAB, (steps, batch, seq)).astype(np.int32)


def _loss(logits, labels):
    return mx.nd.softmax_cross_entropy(
        logits.reshape((-1, VOCAB)), labels.reshape((-1,))) / labels.size


def _step(net):
    mesh = parallel.make_mesh(shape=(1,), axis_names=("dp",),
                              devices=jax.devices()[:1])
    return parallel.TrainStep(net, _loss,
                              mx.optimizer.Adam(learning_rate=1e-3),
                              mesh=mesh)


# -- the equations, written out ------------------------------------------------

def _w(net, name):
    return net.collect_params()["lfm2_" + name].data().asnumpy() \
        .astype(np.float64)


def _norm(x, w):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + EPS) * w


def _silu(x):
    return x / (1 + np.exp(-x))


def _short_conv(net, pre, x):
    """``[B | C | x] = x W_in``; ``z[t] = sum_j w[:, j] (B x)[t - 2 + j]``;
    ``(C z) W_out``."""
    L, K = x.shape[1], CONV["conv_taps"]
    bcx = x @ _w(net, pre + "in_proj_weight").T
    B, C, xs = bcx[..., :UNITS], bcx[..., UNITS:2 * UNITS], bcx[..., 2 * UNITS:]
    v = B * xs
    w = _w(net, pre + "gated_conv_weight")
    z = np.zeros_like(v)
    for t in range(L):
        for j in range(K):
            if t - (K - 1) + j >= 0:
                z[:, t] += w[:, j] * v[:, t - (K - 1) + j]
    return (C * z) @ _w(net, pre + "out_proj_weight").T


def _rope(x, base):
    """x (B, H, L, D): dims i and i + D/2 turned by ``t base^(-2i/D)``."""
    L, D = x.shape[2], x.shape[3]
    half = D // 2
    ang = np.arange(L)[:, None] * base ** (-np.arange(half) / half)[None]
    x1, x2 = x[..., :half], x[..., half:]
    return np.concatenate([x1 * np.cos(ang) - x2 * np.sin(ang),
                           x1 * np.sin(ang) + x2 * np.cos(ang)], -1)


def _attention(net, pre, x):
    B, L, _ = x.shape
    H, KV, D = ATTN["heads"], ATTN["kv_heads"], ATTN["head_dim"]

    def heads(name, n, norm=None):
        h = (x @ _w(net, pre + name + "_proj_weight").T).reshape(B, L, n, D)
        if norm:
            h = _norm(h, _w(net, pre + norm + "_norm_weight"))
        return h.transpose(0, 2, 1, 3)

    q = _rope(heads("q", H, "q"), ATTN["rope_base"])
    k = np.repeat(_rope(heads("k", KV, "k"), ATTN["rope_base"]), H // KV, 1)
    v = np.repeat(heads("v", KV), H // KV, axis=1)
    s = q @ k.transpose(0, 1, 3, 2) / np.sqrt(D)
    s = np.where(np.tril(np.ones((L, L), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    ctx = ((p / p.sum(-1, keepdims=True)) @ v).transpose(0, 2, 1, 3)
    return ctx.reshape(B, L, H * D) @ _w(net, pre + "o_proj_weight").T


def _route(net, pre, x):
    """(N, E) weights: sigmoid scores, the top k of scores + bias, the
    scores at the chosen over their sum + 1e-6."""
    s = 1 / (1 + np.exp(-(x @ _w(net, pre + "router_weight").T)))
    chosen = np.argsort(-(s + _w(net, pre + "router_bias")), -1, kind="stable")
    chosen = chosen[:, :MOE["num_experts_per_token"]]
    picked = np.take_along_axis(s, chosen, -1)
    picked = picked / (picked.sum(-1, keepdims=True) + 1e-6)
    out = np.zeros_like(s)
    np.put_along_axis(out, chosen, picked, -1)
    return out


def _moe(net, pre, x):
    weight = _route(net, pre, x)
    gate, up, down = (_w(net, pre + "experts_" + n)
                      for n in ("gate", "up", "down"))
    first, count = MOE["experts_held"]
    y = np.zeros_like(x)
    for e in range(count):
        h = _silu(x @ gate[e]) * (x @ up[e])
        y += weight[:, first + e:first + e + 1] * (h @ down[e])
    return y


def _layer(net, n, x):
    pre = f"layer{n}_"
    operator, name = (_short_conv, "conv_") if KINDS[n] == "conv" \
        else (_attention, "attn_")
    h = x + operator(net, pre + name,
                     _norm(x, _w(net, pre + "operator_norm_weight")))
    f = _norm(h, _w(net, pre + "ffn_norm_weight"))
    if n == 0:
        g = _silu(f @ _w(net, pre + "ffn_gate_weight").T) \
            * (f @ _w(net, pre + "ffn_up_weight").T)
        return h + g @ _w(net, pre + "ffn_down_weight").T
    return h + _moe(net, pre + "moe_", f.reshape(-1, UNITS)).reshape(h.shape)


def _model(net, tokens):
    E = _w(net, "embed_weight")
    h = E[tokens]
    for n in range(len(KINDS)):
        h = _layer(net, n, h)
    return _norm(h, _w(net, "norm_weight")) @ E.T


@pytest.mark.parametrize("n", [0, 1, 2],
                         ids=["conv_dense", "attention_moe", "conv_moe"])
def test_a_layer_is_its_equations(n):
    net = _net()
    x = np.random.RandomState(1).randn(2, 20, UNITS)
    got = net.layers._children[f"layer{n}"](mx.nd.array(x)).asnumpy()
    np.testing.assert_allclose(got, _layer(net, n, x), rtol=2e-4, atol=2e-5)


def test_B_and_x_are_multiplied_before_the_taps_and_C_after():
    """The op's three parts by their roles: zero B at a position and the
    next K - 1 positions lose what it wrote (C at that position still
    reads); zero C at a position and that position's output is zero and
    no other moves; the convolution is causal."""
    from mxnet_tpu.ops import short_conv
    rs = np.random.RandomState(3)
    u, L = 4, 10
    bcx = rs.randn(1, L, 3 * u)
    w = rs.randn(u, 3)
    base = np.asarray(short_conv._gated_short_conv(bcx, w))
    write0 = bcx.copy()
    write0[:, 5, :u] = 0.0                              # B at position 5
    moved = np.asarray(short_conv._gated_short_conv(write0, w))
    np.testing.assert_allclose(moved[0, :5], base[0, :5], rtol=1e-6)
    np.testing.assert_allclose(moved[0, 8:], base[0, 8:], rtol=1e-6)
    assert np.abs(moved[0, 5:8] - base[0, 5:8]).min() > 0
    read0 = bcx.copy()
    read0[:, 5, u:2 * u] = 0.0                          # C at position 5
    moved = np.asarray(short_conv._gated_short_conv(read0, w))
    np.testing.assert_array_equal(moved[0, 5], 0.0)
    np.testing.assert_allclose(np.delete(moved, 5, 1), np.delete(base, 5, 1),
                               rtol=1e-6)
    x0 = bcx.copy()
    x0[:, 5, 2 * u:] = 0.0                              # x at position 5
    moved = np.asarray(short_conv._gated_short_conv(x0, w))
    np.testing.assert_allclose(moved, np.asarray(
        short_conv._gated_short_conv(write0, w)), rtol=1e-6)


def test_the_op_keeps_the_inputs_type_and_sums_in_float32():
    from mxnet_tpu.ops import short_conv
    rs = np.random.RandomState(4)
    bcx = jnp.asarray(rs.randn(2, 9, 12), jnp.bfloat16)
    w = jnp.asarray(rs.randn(4, 3), jnp.bfloat16)
    got = short_conv._gated_short_conv(bcx, w)
    assert got.dtype == jnp.bfloat16 and got.shape == (2, 9, 4)
    want = short_conv._gated_short_conv(bcx.astype(jnp.float32),
                                        w.astype(jnp.float32))
    np.testing.assert_allclose(np.asarray(got, np.float32), want, rtol=1e-2,
                               atol=1e-2)
    with pytest.raises(ValueError, match="3 x the taps"):
        short_conv._gated_short_conv(bcx[..., :9], w)


def test_the_routers_bias_chooses_and_its_eps_is_the_published_one():
    """The bias moves the choice and not the weights; the chosen weights
    are divided by their sum + 1e-6, which shows where the scores are
    tiny; without ``router_eps`` the op keeps its 1e-20 and its
    attributes (and so its cache key) are what they were."""
    from mxnet_tpu.gluon.contrib import moe
    router = moe._Router(UNITS, 16, 4, 1.0, True, eps=1e-6, prefix="r_")
    router.initialize(mx.init.Normal(0.02))
    rs = np.random.RandomState(6)
    w = rs.randn(16, UNITS)
    router.weight.set_data(mx.nd.array(w))
    x = rs.randn(5, UNITS)
    bias = np.zeros(16)
    bias[[1, 3, 5, 7]] = 10.0                          # the choice, forced
    router.bias.set_data(mx.nd.array(bias))
    weights, chosen = router(mx.nd.array(x))
    assert sorted(map(tuple, np.sort(chosen.asnumpy(), -1))) == \
        [(1, 3, 5, 7)] * 5
    s = 1 / (1 + np.exp(-(x @ w.T)))
    want = s[:, [1, 3, 5, 7]]
    want = want / (want.sum(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(np.sort(weights.asnumpy(), -1),
                               np.sort(want, -1), rtol=1e-5)
    # scores of ~1e-7: the 1e-6 is most of the sum
    tiny = -16.0 * np.ones((1, UNITS)) / np.abs(w).sum(0)
    router.weight.set_data(mx.nd.array(np.abs(w)))
    weights, _ = router(mx.nd.array(tiny))
    s = 1 / (1 + np.exp(-(tiny @ np.abs(w).T)))[0, [1, 3, 5, 7]]
    np.testing.assert_allclose(weights.asnumpy().sum(), s.sum()
                               / (s.sum() + 1e-6), rtol=1e-4)
    assert weights.asnumpy().sum() < 0.5
    assert router._attrs["eps"] == 1e-6
    for score in ("sigmoid", "softmax"):
        assert set(moe._Router(UNITS, 16, 4, 1.0, True, score=score)._attrs) \
            <= {"k", "scale", "normalize", "score"}


def test_the_model_and_its_tied_head_are_the_equations():
    net = _net()
    tok = _tokens()[0]
    logits = net(mx.nd.array(tok, dtype="int32"))
    assert logits.shape == (2, 20, VOCAB)
    np.testing.assert_allclose(logits.asnumpy(), _model(net, tok), rtol=1e-3,
                               atol=2e-5)
    assert not net.lm_head.collect_params()
    assert [n for n in net.collect_params().keys() if "embed" in n] == \
        ["lfm2_embed_weight"]


def _grads_of(net, forward, tok, lab):
    for p in net.collect_params().values():
        if p.grad_req != "null":
            p.zero_grad()
    with autograd.record():
        loss = _loss(forward(mx.nd.array(tok, dtype="int32")),
                     mx.nd.array(lab, dtype="int32"))
    loss.backward()
    return float(loss.asnumpy())


def test_the_tied_weights_gradient_is_the_sum_of_its_two_uses():
    """The embedding and the head read one weight: its gradient is the
    gather's plus the head's, each taken with the other use held by an
    untied copy."""
    net = _net()
    tok, lab = _tokens()[0], _tokens(seed=5)[0]
    loss = _grads_of(net, net, tok, lab)
    tied = net.embed.weight.grad().asnumpy()
    copy = mx.nd.array(net.embed.weight.data().asnumpy())

    def head_is_a_copy(tokens):
        return net.lm_head(net.norm(net.layers(net.embed(tokens))), copy)

    def gather_is_a_copy(tokens):
        x = mx.nd.Embedding(tokens, copy, input_dim=VOCAB, output_dim=UNITS)
        return net.lm_head(net.norm(net.layers(x)), net.embed.weight.data())

    parts = []
    for untied in (head_is_a_copy, gather_is_a_copy):
        assert _grads_of(net, untied, tok, lab) == pytest.approx(loss,
                                                                 rel=1e-6)
        parts.append(net.embed.weight.grad().asnumpy())
    np.testing.assert_allclose(tied, parts[0] + parts[1], rtol=1e-4,
                               atol=1e-6 * np.abs(tied).max())
    assert all(np.abs(g).max() > 1e-3 * np.abs(tied).max() for g in parts)


def test_two_shares_of_the_experts_add_up_to_the_uncut_layer():
    """The guide's share test: the zoo's expert layer holding experts 0-7
    and the one holding 8-15, on the same weights and tokens, add up to the
    plain reference's uncut layer over all 16."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "perfbench"))
    import perfbench_tiny_conv_moe as tiny
    from perfbench import weights
    from perfbench.reference import conv_moe_train as ref
    cfg = tiny.config()
    w = ref.at_init(weights.make_weights(ref.param_shapes(cfg), 9,
                                         "float32"), cfg)
    p = ref._layer_leaves(w, 2)
    x = jax.random.normal(jax.random.PRNGKey(1), (40, UNITS))
    whole = ref.moe_ffn(x, p, cfg, ref.HIGHEST_MATMUL)
    parts = []
    for first in (0, 8):
        layer = mx.gluon.contrib.moe.DroplessMoE(
            UNITS, prefix="moe_", **dict(MOE, experts_held=(first, 8)))
        layer.initialize()
        own = {"router_weight": p["router_w"], "router_bias": p["router_b"]}
        own.update({f"experts_{n}": p[f"experts_{n}"][first:first + 8]
                    for n in ("gate", "up", "down")})
        for name, param in layer.collect_params().items():
            param.set_data(mx.nd.NDArray._from_data(own[name[len("moe_"):]]))
        parts.append(layer(mx.nd.NDArray._from_data(x)).asnumpy())
    np.testing.assert_allclose(parts[0] + parts[1], whole, rtol=1e-4,
                               atol=1e-6)
    assert all(np.abs(part).sum() > 0.1 * np.abs(whole).sum()
               for part in parts)


def test_every_layer_made_again_gives_what_plain_layers_give(monkeypatch):
    """The model's rule (every layer under remat_call, keeping its input)
    against the same model with every layer called plainly: the gradients
    of one recorded pass, and two steps of a TrainStep."""
    tok, lab = _tokens(), _tokens(seed=5)
    net = _net()
    calls = []
    real = lfm2_moe.remat_call
    monkeypatch.setattr(lfm2_moe, "remat_call",
                        lambda blk, x: calls.append(blk.name)
                        or real(blk, x))
    _grads_of(net, net, tok[0], lab[0])
    assert len(calls) == len(KINDS)
    again = {n: p.grad().asnumpy() for n, p in net.collect_params().items()
             if p.grad_req != "null"}
    monkeypatch.setattr(lfm2_moe, "remat_call", lambda blk, x: blk(x))
    _grads_of(net, net, tok[0], lab[0])
    for n, g in again.items():
        np.testing.assert_allclose(
            g, net.collect_params()[n].grad().asnumpy(), rtol=1e-5,
            atol=2e-5 * np.abs(g).max(), err_msg=n)
    del calls[:]
    monkeypatch.setattr(lfm2_moe, "remat_call",
                        lambda blk, x: calls.append(blk.name)
                        or real(blk, x))
    net(mx.nd.array(tok[0], dtype="int32"))     # not recording: none
    assert not calls

    def trained(plain):
        monkeypatch.setattr(lfm2_moe, "remat_call",
                            (lambda blk, x: blk(x)) if plain else real)
        net = _net()
        losses = _step(net).run(mx.nd.array(tok, dtype="int32"),
                                mx.nd.array(lab, dtype="int32")).asnumpy()
        return losses, {n: p.data().asnumpy()
                        for n, p in net.collect_params().items()}

    losses_again, w_again = trained(False)
    losses_plain, w_plain = trained(True)
    np.testing.assert_allclose(losses_again, losses_plain, rtol=1e-6)
    # Adam moves a weight by ~lr a step whatever its gradient's size: the
    # two orders of float32 sums differ by 1e-3 of that
    for n in w_plain:
        np.testing.assert_allclose(w_again[n], w_plain[n], rtol=1e-4,
                                   atol=1e-5, err_msg=n)


# -- names and counters ------------------------------------------------------

def test_children_are_registered_under_the_names_the_regions_read():
    net = _net()
    conv, attn, conv_moe = (net.layers._children[f"layer{i}"]
                            for i in range(3))
    assert set(conv._children) == {"operator_norm", "conv", "ffn_norm",
                                   "ffn"}
    assert set(attn._children) == {"operator_norm", "attn", "ffn_norm",
                                   "moe"}
    assert set(conv_moe._children) == {"operator_norm", "conv", "ffn_norm",
                                       "moe"}
    assert set(conv.conv._children) == {"in_proj", "gated_conv", "out_proj"}
    assert set(attn.attn._children) == {"q_proj", "k_proj", "v_proj",
                                        "q_norm", "k_norm", "o_proj"}
    assert set(attn.moe._children) == {"router", "experts"}
    assert set(net._children) == {"embed", "layers", "norm", "lm_head"}
    # no bias in the model but the routers' expert bias, which takes no
    # gradient
    biases = [(n, p.grad_req) for n, p in net.collect_params().items()
              if n.endswith("bias")]
    assert biases == [("lfm2_layer1_moe_router_bias", "null"),
                      ("lfm2_layer2_moe_router_bias", "null")]


@pytest.fixture(scope="module")
def compiled_step():
    net = _net(held=(0, 8))
    step = _step(net)
    tok = _tokens()
    telemetry.REGISTRY.reset()
    text = step.lowered(tok, tok).compile().as_text()
    passes = {(dict(m.labels)["layer"], dict(m.labels)["kind"]): m.value
              for m in telemetry.REGISTRY.collect()
              if m.name == "mxnet_short_conv_passes_total" and m.value}
    return text, passes


def test_every_instruction_falls_in_a_region_of_the_cell(compiled_step):
    """Every instruction of the compiled step that carries a scope of the
    model, the loss or the optimizer falls in a region of
    perfbench/regions/lfm2_zoo.json, the made-again ones under the names of
    their forward; the op's own scope wins over its operator's."""
    from perfbench import scopes
    text, _ = compiled_step
    rules = scopes.load_regions("lfm2_zoo")
    names = set(re.findall(r'op_name="([^"]*)"', text))
    model = [n for n in names if {"lfm2", "loss", "optimizer"}
             & set(scopes.scope_path(n))]
    assert model
    assert not [n for n in model if scopes.region_of_path(n, rules) is None]
    regions = {scopes.region_of_path(n, rules) for n in model}
    assert {"attention", "gated_conv", "short_conv", "moe_route",
            "moe_experts", "attn_proj", "encoder_dense", "head_loss",
            "optimizer", "other"} <= regions
    again = [n for n in model if "rematted_computation" in n]
    assert {"gated_conv", "short_conv", "attn_proj", "encoder_dense",
            "moe_route", "attention"} <= {scopes.region_of_path(n, rules)
                                          for n in again}
    assert all(scopes.region_of_path(n, rules) == "gated_conv"
               for n in model if "gated_short_conv" in scopes.scope_path(n))


def test_a_traced_step_counts_the_convolutions_passes(compiled_step):
    """Two forward passes a convolution layer and trace: every layer is
    made again."""
    _, passes = compiled_step
    assert set(passes) == {("lfm2/layers/layer0", "made_again"),
                           ("lfm2/layers/layer2", "made_again")}
    traces = {v / 2 for v in passes.values()}
    assert len(traces) == 1 and traces.pop() >= 1


def test_the_routed_counters_leave_the_remade_layers_once():
    """A step whose expert layers run under ``remat_call`` reports the
    routed counters as the same step without the remake does, once a
    traced step and not again for the pass the backward makes."""
    tok, lab = _tokens(), _tokens(seed=5)

    def counted(plain, monkeypatch):
        monkeypatch.setattr(lfm2_moe, "remat_call",
                            (lambda blk, x: blk(x)) if plain
                            else mx.gluon.utils.remat_call)
        telemetry.REGISTRY.reset()
        step = _step(_net(held=(0, 8)))
        step.run(mx.nd.array(tok, dtype="int32"),
                 mx.nd.array(lab, dtype="int32")).asnumpy()
        return {(m.name, dict(m.labels).get("layer")): m.value
                for m in telemetry.REGISTRY.collect()
                if m.name.startswith("mxnet_moe_") and m.value}

    with pytest.MonkeyPatch.context() as monkeypatch:
        again = counted(False, monkeypatch)
        plain = counted(True, monkeypatch)
    assert again == plain
    assert again[("mxnet_moe_tokens_total", None)] == 2 * 2 * 2 * 20
    pairs = [v for (name, _), v in again.items()
             if name == "mxnet_moe_pairs_total"]
    assert len(pairs) == 2 and all(0 < p <= 4 * 2 * 2 * 20 for p in pairs)


def test_the_model_is_the_plain_reference_on_seeded_weights():
    """The zoo model as the benchmark's builder makes it, on the harness's
    seeded weights (``init_scale`` applied), against the plain reference's
    logits, at the tiny configuration of the benchmark's tests."""
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "perfbench"))
    import perfbench_tiny_conv_moe as tiny
    from perfbench import weights
    from perfbench.builders import lfm2_zoo
    from perfbench.reference import conv_moe_train as ref
    cfg = tiny.config()
    w = ref.at_init(weights.make_weights(ref.param_shapes(cfg), 21,
                                         "float32"), cfg)
    model = lfm2_zoo.build_model(cfg)
    for name, p in model.collect_params().items():
        leaf = next(k for k in w if lfm2_zoo._zoo_name(k) == name)
        p.set_data(mx.nd.NDArray._from_data(w[leaf]))
    tok = _tokens(seq=32)[0]
    got = model(mx.nd.array(tok, dtype="int32")).asnumpy()
    np.testing.assert_allclose(got, ref.logits(w, jnp.asarray(tok), cfg),
                               rtol=1e-4, atol=1e-6)
