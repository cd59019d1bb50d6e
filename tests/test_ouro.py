"""The looped language model of the zoo (gluon/model_zoo/ouro.py): a layer
and the loop against their equations written out in NumPy, the exit
distribution, the expected exit loss and its gradient to the gate, a shared
weight's gradient as the sum over its four uses, the rule that makes
activations again against the same model with every application kept, the
names its blocks put into the compiled step forward, backward and in the
instructions made again, and the counter and the gauge it keeps."""

import re

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import autograd, parallel, telemetry
from mxnet_tpu.gluon.model_zoo import ouro

VOCAB, LAYERS, UNITS, HIDDEN, HEADS, HEAD_DIM, STEPS = 64, 2, 32, 48, 2, 16, 4
EPS, BASE, BETA = 1e-6, 1e6, 0.05


def _net(seed=0, steps=STEPS, gate_std=0.5):
    net = ouro.OuroModel(VOCAB, LAYERS, UNITS, HIDDEN, HEADS, HEAD_DIM,
                         loop_steps=steps, eps=EPS, rope_base=BASE,
                         prefix="ouro_")
    net.initialize(mx.init.Normal(0.02))
    rs = np.random.RandomState(seed)
    for name, p in net.collect_params().items():
        # norms off 1 and a gate that spreads p, so that nothing a test
        # holds is hidden by a default
        if name.endswith("norm_weight"):
            p.set_data(mx.nd.array(1 + 0.1 * rs.randn(*p.shape)))
        elif "exit_gate" in name:
            p.set_data(mx.nd.array(gate_std * rs.randn(*p.shape)))
        else:
            p.set_data(mx.nd.array(0.1 * rs.randn(*p.shape)))
    return net


def _tokens(steps=2, batch=2, seq=24, seed=0):
    return np.random.RandomState(seed).randint(
        0, VOCAB, (steps, batch, seq)).astype(np.int32)


def _loss(out, labels):
    return ouro.expected_exit_loss(out, labels, BETA)


def _step(net):
    mesh = parallel.make_mesh(shape=(1,), axis_names=("dp",),
                              devices=jax.devices()[:1])
    return parallel.TrainStep(net, _loss,
                              mx.optimizer.Adam(learning_rate=1e-3),
                              mesh=mesh)


# -- the equations, written out ------------------------------------------------

def _w(net, name):
    return net.collect_params()["ouro_" + name].data().asnumpy() \
        .astype(np.float64)


def _norm(x, w):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + EPS) * w


def _rope(x):       # x (B, H, L, D): pair i = (i, i + D/2)
    L, D = x.shape[2], x.shape[3]
    inv = BASE ** (-np.arange(D // 2) / (D // 2))
    ang = np.arange(L)[:, None] * inv[None]
    a, b = x[..., :D // 2], x[..., D // 2:]
    return np.concatenate([a * np.cos(ang) - b * np.sin(ang),
                           a * np.sin(ang) + b * np.cos(ang)], -1)


def _layer(net, n, x):
    B, L, _ = x.shape
    pre = f"layer{n}_"
    h = _norm(x, _w(net, pre + "attn_in_norm_weight"))

    def heads(name):
        return (h @ _w(net, pre + name).T).reshape(B, L, HEADS, HEAD_DIM) \
            .transpose(0, 2, 1, 3)

    q, k, v = _rope(heads("attn_q_weight")), _rope(heads("attn_k_weight")), \
        heads("attn_v_weight")
    s = q @ k.transpose(0, 1, 3, 2) / np.sqrt(HEAD_DIM)
    s = np.where(np.tril(np.ones((L, L), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    ctx = (p / p.sum(-1, keepdims=True)) @ v
    ctx = ctx.transpose(0, 2, 1, 3).reshape(B, L, HEADS * HEAD_DIM)
    a = x + _norm(ctx @ _w(net, pre + "attn_o_weight").T,
                  _w(net, pre + "attn_out_norm_weight"))
    h = _norm(a, _w(net, pre + "mlp_in_norm_weight"))
    g = h @ _w(net, pre + "mlp_gate_weight").T
    h = g / (1 + np.exp(-g)) * (h @ _w(net, pre + "mlp_up_weight").T)
    return a + _norm(h @ _w(net, pre + "mlp_down_weight").T,
                     _w(net, pre + "mlp_out_norm_weight"))


def _model(net, tokens, steps=STEPS):
    """(logits (T, B, L, V), p (T, B, L)) by the equations."""
    h = _w(net, "tok_weight")[tokens]
    hs = []
    for _ in range(steps):
        for n in range(LAYERS):
            h = _layer(net, n, h)
        h = _norm(h, _w(net, "final_norm_weight"))
        hs.append(h)
    hs = np.stack(hs)
    lam = 1 / (1 + np.exp(-(hs @ _w(net, "exit_gate_weight").T
                            + _w(net, "exit_gate_bias"))[..., 0]))
    left, p = np.ones_like(lam[0]), []
    for t in range(steps - 1):
        p.append(lam[t] * left)
        left = left * (1 - lam[t])
    return hs @ _w(net, "lm_head_weight").T, np.stack(p + [left])


def _expected_loss(logits, p, labels):
    z = logits - logits.max(-1, keepdims=True)
    logp = z - np.log(np.exp(z).sum(-1, keepdims=True))
    ce = -np.take_along_axis(logp, labels[None, ..., None]
                             .repeat(len(logits), 0), -1)[..., 0]
    return ((p * ce).sum(0) + BETA * (p * np.log(p)).sum(0)).mean()


def test_a_layer_is_the_sandwich_norm_block_of_the_equations():
    net = _net()
    x = np.random.RandomState(1).randn(2, 24, UNITS)
    got = net.layers._children["layer1"](mx.nd.array(x)).asnumpy()
    np.testing.assert_allclose(got, _layer(net, 1, x), rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("steps", [4, 1, 3])
def test_the_loop_the_gate_and_the_head_are_the_equations(steps):
    net = _net(steps=steps)
    tok = _tokens()[0]
    logits, p = net(mx.nd.array(tok, dtype="int32"))
    want_logits, want_p = _model(net, tok, steps)
    assert logits.shape == (steps, 2, 24, VOCAB) and p.shape == (steps, 2, 24)
    assert p.dtype == np.float32
    np.testing.assert_allclose(logits.asnumpy(), want_logits, rtol=2e-3,
                               atol=2e-4)
    np.testing.assert_allclose(p.asnumpy(), want_p, rtol=1e-3, atol=1e-6)
    # a position's exit distribution sums to one, whatever the gate says
    np.testing.assert_allclose(p.asnumpy().sum(0), 1.0, atol=1e-6)


def test_the_exit_distribution_sums_to_one_with_a_saturated_gate():
    net = _net(gate_std=30.0)       # lam of 0 and 1 to rounding
    _, p = net(mx.nd.array(_tokens()[0], dtype="int32"))
    p = p.asnumpy()
    assert (p == 0).any()
    np.testing.assert_allclose(p.sum(0), 1.0, atol=1e-6)
    labels = mx.nd.array(_tokens(seed=5)[0], dtype="int32")
    loss = _loss(net(mx.nd.array(_tokens()[0], dtype="int32")), labels)
    assert np.isfinite(loss.asnumpy())      # 0 log 0 counts as 0


def test_the_loss_is_the_expected_exit_loss_less_beta_entropy():
    net = _net()
    tok, lab = _tokens()[0], _tokens(seed=5)[0]
    got = _loss(net(mx.nd.array(tok, dtype="int32")),
                mx.nd.array(lab, dtype="int32")).asnumpy()
    logits, p = _model(net, tok)
    assert got == pytest.approx(_expected_loss(logits, p, lab), rel=1e-4)


def test_the_gates_gradient_agrees_with_finite_differences():
    net = _net()
    tok, lab = _tokens()[0], _tokens(seed=5)[0]
    with autograd.record():
        loss = _loss(net(mx.nd.array(tok, dtype="int32")),
                     mx.nd.array(lab, dtype="int32"))
    loss.backward()
    gate = net.collect_params()["ouro_exit_gate_weight"]
    bias = net.collect_params()["ouro_exit_gate_bias"]
    got_w, got_b = gate.grad().asnumpy()[0], bias.grad().asnumpy()[0]
    logits, _ = _model(net, tok)    # the gate moves p alone
    w0, b0 = _w(net, "exit_gate_weight"), _w(net, "exit_gate_bias")

    def at(w, b):
        gate.set_data(mx.nd.array(w))
        bias.set_data(mx.nd.array(b))
        value = _expected_loss(logits, _model(net, tok)[1], lab)
        gate.set_data(mx.nd.array(w0))
        bias.set_data(mx.nd.array(b0))
        return value

    h = 1e-3
    for i in (0, 7, 31):
        d = np.zeros_like(w0)
        d[0, i] = h
        want = (at(w0 + d, b0) - at(w0 - d, b0)) / (2 * h)
        assert got_w[i] == pytest.approx(want, rel=2e-2, abs=1e-6)
    want = (at(w0, b0 + h) - at(w0, b0 - h)) / (2 * h)
    assert got_b == pytest.approx(want, rel=2e-2, abs=1e-6)
    assert abs(got_b) > 1e-5        # the gate does take a gradient


# -- one set of weights, four uses ----------------------------------------------

def _grads_of(net, forward, tok, lab):
    for p in net.collect_params().values():
        p.zero_grad()
    with autograd.record():
        loss = _loss(forward(mx.nd.array(tok, dtype="int32")),
                     mx.nd.array(lab, dtype="int32"))
    loss.backward()
    return float(loss.asnumpy())


def test_a_shared_weights_gradient_is_the_sum_over_four_untied_copies():
    net = _net()
    tok, lab = _tokens()[0], _tokens(seed=5)[0]
    copies = []
    for t in range(STEPS):
        stack = ouro._Layers([
            ouro.OuroDecoderLayer(UNITS, HIDDEN, HEADS, HEAD_DIM, eps=EPS,
                                  rope_base=BASE, prefix=f"layer{i}_")
            for i in range(LAYERS)], prefix=f"use{t}_")
        stack.initialize()
        for name, p in stack.collect_params().items():
            p.set_data(net.collect_params()["ouro_" + name].data())
        copies.append(stack)

    def untied(tokens):
        x, exits = net.embed(tokens), []
        for stack in copies:
            x = net.norm(stack(x))
            exits.append(x)
        hs = mx.nd.stack(*exits, axis=0)
        return net.lm_head(hs), net.exit_gate(hs)

    loss_untied = _grads_of(net, untied, tok, lab)
    per_use = [{name: p.grad().asnumpy()
                for name, p in stack.collect_params().items()}
               for stack in copies]
    loss_tied = _grads_of(net, net, tok, lab)
    assert loss_tied == pytest.approx(loss_untied, rel=1e-6)
    for name in per_use[0]:
        got = net.collect_params()["ouro_" + name].grad().asnumpy()
        parts = [g[name] for g in per_use]
        np.testing.assert_allclose(got, sum(parts), rtol=2e-4,
                                   atol=2e-5 * np.abs(got).max())
        # and no single use gives it: every use carries gradient
        assert all(np.abs(g).max() > 0 for g in parts)
        assert not np.allclose(got, parts[-1], rtol=1e-2, atol=0)


def test_made_again_gives_the_losses_and_gradients_of_everything_kept(
        monkeypatch):
    """The model's rule (loop steps before the last under remat_call)
    against the same model with every application called plainly: the
    gradients of one recorded pass, and two steps of a TrainStep."""
    tok, lab = _tokens(), _tokens(seed=5)
    net = _net()
    calls = []
    real = ouro.remat_call
    monkeypatch.setattr(ouro, "remat_call",
                        lambda blk, x: calls.append(blk) or real(blk, x))
    _grads_of(net, net, tok[0], lab[0])
    assert len(calls) == (STEPS - 1) * LAYERS
    again = {n: p.grad().asnumpy() for n, p in net.collect_params().items()}
    monkeypatch.setattr(ouro, "remat_call", lambda blk, x: blk(x))
    _grads_of(net, net, tok[0], lab[0])
    for n, p in net.collect_params().items():
        np.testing.assert_allclose(again[n], p.grad().asnumpy(), rtol=1e-5,
                                   atol=2e-5 * np.abs(again[n]).max(),
                                   err_msg=n)
    # outside a recording nothing is made again
    monkeypatch.setattr(ouro, "remat_call", real)
    del calls[:]
    monkeypatch.setattr(ouro, "remat_call",
                        lambda blk, x: calls.append(blk) or real(blk, x))
    net(mx.nd.array(tok[0], dtype="int32"))
    assert not calls

    def trained(kept):
        if kept:
            monkeypatch.setattr(ouro, "remat_call", lambda blk, x: blk(x))
        else:
            monkeypatch.setattr(ouro, "remat_call", real)
        net = _net()
        losses = _step(net).run(mx.nd.array(tok, dtype="int32"),
                                mx.nd.array(lab, dtype="int32")).asnumpy()
        return losses, {n: p.data().asnumpy()
                        for n, p in net.collect_params().items()}

    losses_again, w_again = trained(False)
    losses_kept, w_kept = trained(True)
    np.testing.assert_allclose(losses_again, losses_kept, rtol=1e-6)
    for n in w_kept:
        np.testing.assert_allclose(w_again[n], w_kept[n], rtol=1e-4,
                                   atol=1e-6, err_msg=n)


# -- names, counter, gauge ---------------------------------------------------

@pytest.fixture(scope="module")
def compiled_step():
    net = _net()
    step = _step(net)
    tok = _tokens()
    text = step.lowered(tok, tok).compile().as_text()
    return net, step, tok, text


def test_children_are_registered_under_the_names_the_regions_read():
    net = _net()
    layer = net.layers._children["layer0"]
    assert set(layer._children) == {"attn", "mlp"}
    assert set(layer.attn._children) == {"in_norm", "q_proj", "k_proj",
                                         "v_proj", "o_proj", "out_norm"}
    assert set(layer.mlp._children) == {"in_norm", "gate", "up", "down",
                                        "out_norm"}
    assert {"embed", "layers", "norm", "lm_head", "exit_gate"} \
        <= set(net._children)
    # one bias in the model: the exit gate's
    assert [n for n in net.collect_params().keys() if n.endswith("bias")] \
        == ["ouro_exit_gate_bias"]


def test_made_again_instructions_carry_their_forwards_region(compiled_step):
    """Every instruction of the lowered step that carries a scope of the
    model, the loss or the optimizer falls in a region of
    perfbench/regions/ouro_zoo.json; every region a loop step has shows
    under each ``loop<t>``; and the instructions the backward makes again
    (``rematted_computation``) read the scopes of their forward."""
    from perfbench import scopes
    _, _, _, text = compiled_step
    rules = scopes.load_regions("ouro_zoo")
    names = set(re.findall(r'op_name="([^"]*)"', text))
    model = [n for n in names if {"ouro", "loss", "optimizer"}
             & set(scopes.scope_path(n))]
    assert model
    unnamed = [n for n in model if scopes.region_of_path(n, rules) is None]
    assert not unnamed, unnamed[:5]
    assert {"attention", "attn_proj", "encoder_dense", "head_loss",
            "optimizer", "other"} <= {scopes.region_of_path(n, rules)
                                      for n in model}
    in_a_step = {"attention", "attn_proj", "encoder_dense"}
    for t in range(1, STEPS + 1):
        mine = [n for n in model if f"loop{t}" in scopes.scope_path(n)]
        assert in_a_step <= {scopes.region_of_path(n, rules) for n in mine}
        assert any("transpose(" in n for n in mine), t
        again = [n for n in mine if "rematted_computation" in n]
        if t < STEPS:       # made again: under the names of its forward
            assert in_a_step <= {scopes.region_of_path(n, rules)
                                 for n in again}, t
            assert all(re.search(r"layer\d", n) for n in again)
        else:               # the last step keeps its activations
            assert not again


def test_a_traced_step_counts_its_layer_passes_and_reports_the_exit_mass(
        compiled_step):
    net, step, tok, _ = compiled_step
    telemetry.REGISTRY.reset()
    losses = step.run(mx.nd.array(tok, dtype="int32"),
                      mx.nd.array(tok, dtype="int32")).asnumpy()
    assert np.isfinite(losses).all()
    passes = {dict(m.labels)["kind"]: m.value
              for m in telemetry.REGISTRY.collect()
              if m.name == "mxnet_loop_layer_passes_total"
              and dict(m.labels)["model"] == "ouro"}
    # a trace of the step: the last loop step's layers once each, the
    # three before it twice each (first, and again in the backward)
    assert passes["kept"] % LAYERS == 0 and passes["kept"] > 0
    assert passes["made_again"] == 2 * (STEPS - 1) * passes["kept"]
    mass = {dict(m.labels)["step"]: m.value
            for m in telemetry.REGISTRY.collect()
            if m.name == "mxnet_loop_exit_mass"}
    assert set(mass) == {"1", "2", "3", "4"}
    assert sum(mass.values()) == pytest.approx(1.0, abs=1e-5)
    # the gauge is the mean of p over the dispatch's positions: both
    # steps' batches, the second on weights one small update on
    start = _net()
    want = np.mean([_model(start, t)[1].mean(axis=(1, 2)) for t in tok],
                   axis=0)
    for t in range(STEPS):
        assert mass[str(t + 1)] == pytest.approx(want[t], abs=0.02)
    # the imperative forward outside any trace counts nothing
    before = dict(passes)
    net(mx.nd.array(tok[0], dtype="int32"))
    after = {dict(m.labels)["kind"]: m.value
             for m in telemetry.REGISTRY.collect()
             if m.name == "mxnet_loop_layer_passes_total"}
    assert after == before


def test_a_mean_report_is_a_gauge_of_the_last_dispatch():
    """``parallel.report_counter(kind="mean")``: the gauge holds the mean
    over elements and steps of the dispatch fetched last; a second mean
    under one name in one step is refused."""
    from mxnet_tpu.base import MXNetError
    with parallel._collect_reports() as sink:
        parallel.report_counter("t_mean", np.array([1.0, 3.0]), kind="mean")
        with pytest.raises(MXNetError, match="twice"):
            parallel.report_counter("t_mean", 1.0, kind="mean")
        with pytest.raises(MXNetError, match="sum|max|mean"):
            parallel.report_counter("t_mean", 1.0, kind="median")
    assert float(sink[("t_mean", (), "mean")]) == 2.0
    parallel._bank_reports({("t_mean", (), "mean"): np.array([2.0, 4.0])})
    parallel._bank_reports({("t_mean", (), "mean"): np.array([1.0, 2.0])})
    assert telemetry.REGISTRY.get("t_mean").value == 1.5


def test_a_bfloat16_multi_precision_step_moves_every_master():
    import ml_dtypes
    net = _net()
    net.cast(ml_dtypes.bfloat16)
    before = {n: p.data().asnumpy().astype("float32")
              for n, p in net.collect_params().items()}
    mesh = parallel.make_mesh(shape=(1,), axis_names=("dp",),
                              devices=jax.devices()[:1])
    step = parallel.TrainStep(
        net, _loss, mx.optimizer.Adam(learning_rate=1e-3,
                                      multi_precision=True), mesh=mesh)
    tok = _tokens(seed=1)
    with jax.default_matmul_precision("default"):
        losses = step.run(mx.nd.array(tok, dtype="int32"),
                          mx.nd.array(tok, dtype="int32")).asnumpy()
    assert np.isfinite(losses).all()
    for n, p in net.collect_params().items():
        assert not np.array_equal(before[n],
                                  p.data().asnumpy().astype("float32")), n
