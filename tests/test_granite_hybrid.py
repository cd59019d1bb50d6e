"""The hybrid Mamba-2 + attention decoder of the zoo
(gluon/model_zoo/granite_hybrid.py): a layer of each kind against its
equations written out in NumPy (the scan token by token, the gate before
the norm, B and C by their roles), the model and its tied head, the tied
weight's gradient as the sum of its two uses, the rule that makes every
layer again against the same model called plainly, the names its blocks put
into the compiled step, and the counters it and ``remat_call`` keep; the
model against the plain reference on seeded weights."""

import re

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import autograd, parallel, telemetry
from mxnet_tpu.gluon.model_zoo import granite_hybrid as gh

VOCAB, UNITS, HIDDEN = 64, 32, 48
MAMBA = dict(heads=4, head_dim=8, groups=2, state=16, conv_taps=4, chunk=8)
ATTN = dict(heads=4, kv_heads=2, head_dim=8, multiplier=1 / 16)
KINDS = ["mamba", "attention", "mamba"]
EPS, EMBED, RESIDUAL, SCALING = 1e-5, 12.0, 0.22, 8.0


def _net(seed=0, kinds=KINDS):
    net = gh.GraniteHybridModel(
        VOCAB, kinds, UNITS, HIDDEN, MAMBA, ATTN, EMBED, RESIDUAL, SCALING,
        eps=EPS, prefix="granite_")
    net.initialize(mx.init.Normal(0.02))
    rs = np.random.RandomState(seed)
    for name, p in net.collect_params().items():
        # every leaf off its default, so that nothing a test holds is
        # hidden by a 1 or a 0; decays that keep ~0.9 a position
        if name.endswith(("norm_weight", "scan_D")):
            value = 1 + 0.1 * rs.randn(*p.shape)
        elif name.endswith("A_log"):
            value = -1 + 0.3 * rs.randn(*p.shape)
        elif name.endswith("dt_bias"):
            value = -2 + 0.3 * rs.randn(*p.shape)
        elif name.endswith("conv_weight"):
            value = 0.3 * rs.randn(*p.shape)
        else:
            value = 0.1 * rs.randn(*p.shape)
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
    return net.collect_params()["granite_" + name].data().asnumpy() \
        .astype(np.float64)


def _norm(x, w):
    return x / np.sqrt((x * x).mean(-1, keepdims=True) + EPS) * w


def _silu(x):
    return x / (1 + np.exp(-x))


def _mamba(net, pre, x):
    """The mixer: W_in, the convolution, the scan token by token, the gate
    before the norm, W_out."""
    B, L, _ = x.shape
    H, P, G, N, K = (MAMBA[k] for k in ("heads", "head_dim", "groups",
                                        "state", "conv_taps"))
    inner, conv = H * P, H * P + 2 * G * N
    zxd = x @ _w(net, pre + "in_proj_weight").T
    z, xbc, dt = zxd[..., :inner], zxd[..., inner:inner + conv], \
        zxd[..., inner + conv:]
    cw, cb = _w(net, pre + "conv_weight"), _w(net, pre + "conv_bias")
    padded = np.concatenate([np.zeros((B, K - 1, conv)), xbc], 1)
    xbc = _silu(sum(padded[:, j:j + L] * cw[:, j] for j in range(K)) + cb)
    xs = xbc[..., :inner].reshape(B, L, H, P)
    Bm = xbc[..., inner:inner + G * N].reshape(B, L, G, N)
    Cm = xbc[..., inner + G * N:].reshape(B, L, G, N)
    dt = np.log1p(np.exp(dt + _w(net, pre + "scan_dt_bias")))
    A = -np.exp(_w(net, pre + "scan_A_log"))
    D = _w(net, pre + "scan_D")
    y = np.zeros((B, L, H, P))
    for b in range(B):
        for h in range(H):
            g = h // (H // G)               # heads of a group are neighbours
            state = np.zeros((P, N))
            for t in range(L):
                state = np.exp(dt[b, t, h] * A[h]) * state \
                    + dt[b, t, h] * np.outer(xs[b, t, h], Bm[b, t, g])
                y[b, t, h] = state @ Cm[b, t, g] + D[h] * xs[b, t, h]
    o = _norm(y.reshape(B, L, inner) * _silu(z), _w(net, pre + "norm_weight"))
    return o @ _w(net, pre + "out_proj_weight").T


def _attention(net, pre, x):
    B, L, _ = x.shape
    H, KV, D = ATTN["heads"], ATTN["kv_heads"], ATTN["head_dim"]

    def heads(name, n):
        return (x @ _w(net, pre + name).T).reshape(B, L, n, D) \
            .transpose(0, 2, 1, 3)

    q = heads("q_proj_weight", H)
    k = np.repeat(heads("k_proj_weight", KV), H // KV, axis=1)
    v = np.repeat(heads("v_proj_weight", KV), H // KV, axis=1)
    s = q @ k.transpose(0, 1, 3, 2) * ATTN["multiplier"]
    s = np.where(np.tril(np.ones((L, L), bool)), s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    ctx = ((p / p.sum(-1, keepdims=True)) @ v).transpose(0, 2, 1, 3)
    return ctx.reshape(B, L, H * D) @ _w(net, pre + "o_proj_weight").T


def _layer(net, n, x):
    pre = f"layer{n}_"
    mixer, name = (_mamba, "mamba_") if KINDS[n] == "mamba" \
        else (_attention, "attn_")
    a = x + RESIDUAL * mixer(net, pre + name,
                             _norm(x, _w(net, pre + "in_norm_weight")))
    h = _norm(a, _w(net, pre + "post_norm_weight"))
    gu = h @ _w(net, pre + "mlp_input_linear_weight").T
    h = _silu(gu[..., :HIDDEN]) * gu[..., HIDDEN:]
    return a + RESIDUAL * h @ _w(net, pre + "mlp_output_linear_weight").T


def _model(net, tokens):
    E = _w(net, "embed_weight")
    h = E[tokens] * EMBED
    for n in range(len(KINDS)):
        h = _layer(net, n, h)
    return _norm(h, _w(net, "final_norm_weight")) @ E.T / SCALING


@pytest.mark.parametrize("n", [0, 1], ids=["mamba", "attention"])
def test_a_layer_is_its_equations(n):
    net = _net()
    x = np.random.RandomState(1).randn(2, 20, UNITS)
    got = net.layers._children[f"layer{n}"](mx.nd.array(x)).asnumpy()
    np.testing.assert_allclose(got, _layer(net, n, x), rtol=2e-4, atol=2e-5)


def test_the_gate_comes_before_the_norm():
    """``rmsnorm(y * silu(z)) w`` over all the channels, which a norm per
    head then a gate (Qwen3-Next's order) is not."""
    net = _net()
    norm = net.layers._children["layer0"].mamba.norm
    rs = np.random.RandomState(2)
    y, z = rs.randn(1, 5, 32), rs.randn(1, 5, 32)
    w = norm.weight.data().asnumpy()
    got = norm(mx.nd.array(y), mx.nd.array(z)).asnumpy()
    np.testing.assert_allclose(got, _norm(y * _silu(z), w), rtol=1e-5,
                               atol=1e-6)
    by_head = (_norm(y.reshape(1, 5, 4, 8), 1.0).reshape(1, 5, 32) * w
               * _silu(z))
    assert np.abs(got - by_head).max() > 0.1


def test_B_writes_and_C_reads():
    """A position's write goes through B and its read through C: zero C at
    a position and that position's scan output is ``D x`` alone; zero B and
    no position after it reads what it wrote."""
    net = _net()
    scan = net.layers._children["layer0"].mamba.scan
    H, P, G, N = 4, 8, 2, 16
    rs = np.random.RandomState(3)
    xbc = rs.randn(1, 12, H * P + 2 * G * N)
    dt = rs.randn(1, 12, H)
    x = xbc[..., :H * P].reshape(1, 12, H, P)
    D = scan.D.data().asnumpy()
    read0 = xbc.copy()
    read0[:, 5, H * P + G * N:] = 0.0               # C at position 5
    got = scan(mx.nd.array(read0), mx.nd.array(dt)).asnumpy()
    np.testing.assert_allclose(got[0, 5].reshape(H, P), D[:, None] * x[0, 5],
                               rtol=1e-5, atol=1e-6)
    write0 = xbc.copy()
    write0[:, 5, H * P:H * P + G * N] = 0.0         # B at position 5
    base = scan(mx.nd.array(xbc), mx.nd.array(dt)).asnumpy()
    moved = scan(mx.nd.array(write0), mx.nd.array(dt)).asnumpy()
    np.testing.assert_allclose(moved[0, :5], base[0, :5], rtol=1e-6)
    assert np.abs(moved[0, 6:] - base[0, 6:]).max() > 1e-3


def test_the_model_and_its_tied_head_are_the_equations():
    net = _net()
    tok = _tokens()[0]
    logits = net(mx.nd.array(tok, dtype="int32"))
    assert logits.shape == (2, 20, VOCAB)
    np.testing.assert_allclose(logits.asnumpy(), _model(net, tok), rtol=1e-3,
                               atol=2e-5)
    # the head has no weight of its own
    assert not net.lm_head.collect_params()
    assert [n for n in net.collect_params().keys() if "embed" in n] == \
        ["granite_embed_weight"]


def _grads_of(net, forward, tok, lab):
    for p in net.collect_params().values():
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
        x = net.embed(tokens) * EMBED
        return net.lm_head(net.norm(net.layers(x)), copy)

    def gather_is_a_copy(tokens):
        x = mx.nd.Embedding(tokens, copy, input_dim=VOCAB,
                            output_dim=UNITS) * EMBED
        return net.lm_head(net.norm(net.layers(x)), net.embed.weight.data())

    parts = []
    for untied in (head_is_a_copy, gather_is_a_copy):
        assert _grads_of(net, untied, tok, lab) == pytest.approx(loss,
                                                                 rel=1e-6)
        parts.append(net.embed.weight.grad().asnumpy())
    np.testing.assert_allclose(tied, parts[0] + parts[1], rtol=1e-4,
                               atol=1e-6 * np.abs(tied).max())
    assert all(np.abs(g).max() > 1e-3 * np.abs(tied).max() for g in parts)


def test_every_layer_made_again_gives_what_plain_layers_give(monkeypatch):
    """The model's rule (every layer under remat_call, keeping its input)
    against the same model with every layer called plainly: the gradients
    of one recorded pass, and two steps of a TrainStep."""
    tok, lab = _tokens(), _tokens(seed=5)
    net = _net()
    calls = []
    real = gh.remat_call
    monkeypatch.setattr(gh, "remat_call",
                        lambda blk, x: calls.append(blk.name)
                        or real(blk, x))
    _grads_of(net, net, tok[0], lab[0])
    assert len(calls) == len(KINDS)
    again = {n: p.grad().asnumpy() for n, p in net.collect_params().items()}
    monkeypatch.setattr(gh, "remat_call", lambda blk, x: blk(x))
    _grads_of(net, net, tok[0], lab[0])
    for n, p in net.collect_params().items():
        np.testing.assert_allclose(again[n], p.grad().asnumpy(), rtol=1e-5,
                                   atol=2e-5 * np.abs(again[n]).max(),
                                   err_msg=n)
    # outside a recording nothing is made again
    monkeypatch.setattr(gh, "remat_call",
                        lambda blk, x: calls.append(blk.name)
                        or real(blk, x))
    del calls[:]
    net(mx.nd.array(tok[0], dtype="int32"))
    assert not calls

    def trained(plain):
        monkeypatch.setattr(gh, "remat_call",
                            (lambda blk, x: blk(x)) if plain else real)
        net = _net()
        losses = _step(net).run(mx.nd.array(tok, dtype="int32"),
                                mx.nd.array(lab, dtype="int32")).asnumpy()
        return losses, {n: p.data().asnumpy()
                        for n, p in net.collect_params().items()}

    losses_again, w_again = trained(False)
    losses_plain, w_plain = trained(True)
    np.testing.assert_allclose(losses_again, losses_plain, rtol=1e-6)
    for n in w_plain:
        np.testing.assert_allclose(w_again[n], w_plain[n], rtol=1e-4,
                                   atol=1e-6, err_msg=n)


# -- names and counters ------------------------------------------------------

def test_children_are_registered_under_the_names_the_regions_read():
    net = _net()
    mamba, attn = (net.layers._children[f"layer{i}"] for i in (0, 1))
    assert set(mamba._children) == {"in_norm", "mamba", "post_norm", "mlp"}
    assert set(attn._children) == {"in_norm", "attn", "post_norm", "mlp"}
    assert set(mamba.mamba._children) == {"in_proj", "conv", "scan", "norm",
                                          "out_proj"}
    assert set(attn.attn._children) == {"q_proj", "k_proj", "v_proj",
                                        "o_proj"}
    assert set(mamba.mlp._children) == {"input_linear", "output_linear"}
    assert set(net._children) == {"embed", "layers", "norm", "lm_head"}
    # one bias in the model: the convolution's, a Mamba-2 layer
    assert [n for n in net.collect_params().keys() if n.endswith("bias")
            and "dt_bias" not in n] == ["granite_layer0_mamba_conv_bias",
                                        "granite_layer2_mamba_conv_bias"]


@pytest.fixture(scope="module")
def compiled_step():
    net = _net()
    step = _step(net)
    tok = _tokens()
    telemetry.REGISTRY.reset()
    text = step.lowered(tok, tok).compile().as_text()
    chunks = {dict(m.labels)["layer"]: m.value
              for m in telemetry.REGISTRY.collect()
              if m.name == "mxnet_ssd_chunks_total" and m.value}
    return text, chunks


def test_every_instruction_falls_in_a_region_of_the_cell(compiled_step):
    """Every instruction of the compiled step that carries a scope of the
    model, the loss or the optimizer falls in a region of
    perfbench/regions/granite_zoo.json, the made-again ones under the
    names of their forward; the scan's own scope wins over its mixer's."""
    from perfbench import scopes
    text, _ = compiled_step
    rules = scopes.load_regions("granite_zoo")
    names = set(re.findall(r'op_name="([^"]*)"', text))
    model = [n for n in names if {"granite", "loss", "optimizer"}
             & set(scopes.scope_path(n))]
    assert model
    assert not [n for n in model if scopes.region_of_path(n, rules) is None]
    regions = {scopes.region_of_path(n, rules) for n in model}
    assert {"attention", "ssd_scan", "ssm_proj", "attn_proj",
            "encoder_dense", "head_loss", "optimizer", "other"} <= regions
    again = [n for n in model if "rematted_computation" in n]
    assert {"ssd_scan", "ssm_proj", "attn_proj", "encoder_dense",
            "attention"} <= {scopes.region_of_path(n, rules) for n in again}
    assert all(scopes.region_of_path(n, rules) == "ssd_scan"
               for n in model if "ssd_scan" in scopes.scope_path(n))


def test_a_traced_forward_counts_the_scans_chunks(compiled_step):
    """batch x heads x chunks a Mamba-2 layer and trace, labelled by the
    scan's region path: 2 rows x 4 heads x ceil(20 / 8) chunks."""
    _, chunks = compiled_step
    assert chunks and set(chunks) == {
        "granite/layers/layer0/mamba/scan", "granite/layers/layer2/mamba/scan"}
    traces = {v / (2 * 4 * 3) for v in chunks.values()}
    assert len(traces) == 1 and traces.pop() >= 1


def test_remat_counts_an_application_a_layer_at_the_cells_depth():
    """The cell's ten layers (layer_types' first ten: nine Mamba-2 and the
    attention at index 5), each made again keeping its input alone: ten
    ``keep="inputs"`` applications a trace, none with a policy, and the
    kept bytes those of the layers' inputs."""
    kinds = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
    net = _net(kinds=kinds)
    tok = _tokens(batch=1, seq=16)
    telemetry.REGISTRY.reset()
    with autograd.record():
        net(mx.nd.array(tok[0], dtype="int32"))
    # `reset` zeroes series in place and keeps them: rows another test
    # left read 0 here
    counters = {(m.name, dict(m.labels)["keep"]): m.value
                for m in telemetry.REGISTRY.collect()
                if m.name.startswith("mxnet_remat_") and m.value}
    assert counters == {
        ("mxnet_remat_applications_total", "inputs"): 10,
        ("mxnet_remat_kept_bytes_total", "inputs"): 10 * 16 * UNITS * 4}


def test_the_model_is_the_plain_reference_on_seeded_weights():
    """The zoo model as the benchmark's builder makes it, on the harness's
    seeded weights (``init_scale`` applied), against the plain reference's
    logits, at the tiny configuration of the benchmark's tests."""
    import sys
    import os
    import jax.numpy as jnp
    sys.path.insert(0, os.path.join(os.path.dirname(__file__), "perfbench"))
    import perfbench_tiny_ssm_hybrid as tiny
    from perfbench import weights
    from perfbench.builders import granite_zoo
    from perfbench.reference import ssm_hybrid_train as ref
    cfg = tiny.config()
    w = ref.at_init(weights.make_weights(ref.param_shapes(cfg), 21,
                                         "float32"), cfg)
    model = granite_zoo.build_model(cfg)
    for name, p in model.collect_params().items():
        leaf = next(k for k in w if granite_zoo._zoo_name(k) == name)
        p.set_data(mx.nd.NDArray._from_data(w[leaf]))
    tok = _tokens(seq=32)[0]
    got = model(mx.nd.array(tok, dtype="int32")).asnumpy()
    np.testing.assert_allclose(got, ref.logits(w, jnp.asarray(tok), cfg),
                               rtol=1e-4, atol=1e-6)
