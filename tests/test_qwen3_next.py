"""The hybrid Gated DeltaNet + gated attention + MoE decoder of the zoo
(gluon/model_zoo/qwen3_next.py): the layer pattern, the names its blocks
put into the compiled step forward and backward (every one a scope the
benchmark's region file names), the zero-centred norm, the two mixers
against their equations written out in NumPy, and the counter the scan
grows."""

import re

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import parallel, telemetry
from mxnet_tpu.gluon.model_zoo import qwen3_next
from mxnet_tpu.gluon.model_zoo.llama import RMSNorm

ATT = dict(heads=4, kv_heads=2, head_dim=32, rotary_dim=8, rope_base=1e7)
LIN = dict(key_heads=2, value_heads=4, key_dim=16, value_dim=16, conv_taps=4)
MOE = dict(hidden_size=32, num_experts=16, num_experts_per_token=3,
           experts_held=(4, 8), num_shared_experts=1)


def _net(layers=4, dtype=None):
    net = qwen3_next.Qwen3NextModel(256, layers, 64, ATT, LIN, MOE,
                                    prefix="qwen3next_")
    net.initialize(mx.init.Normal(0.02))
    if dtype:
        net.cast(dtype)
    return net


def _loss(logits, labels):
    return mx.nd.softmax_cross_entropy(
        logits.reshape((-1, logits.shape[-1])).astype("float32"),
        labels.reshape((-1,))) / labels.size


def _step(net, multi_precision=False):
    opt = mx.optimizer.Adam(learning_rate=1e-3,
                            multi_precision=multi_precision)
    mesh = parallel.make_mesh(shape=(1,), axis_names=("dp",),
                              devices=jax.devices()[:1])
    return parallel.TrainStep(net, _loss, opt, mesh=mesh)


def _tokens(steps=2, batch=2, seq=96, seed=0):
    return np.random.RandomState(seed).randint(
        0, 256, (steps, batch, seq)).astype(np.int32)


@pytest.mark.parametrize("layers, full", [(4, [3]), (8, [3, 7]), (3, [])])
def test_every_fourth_layer_runs_full_attention(layers, full):
    net = _net(layers)
    kinds = [net.layers._children[f"layer{i}"] for i in range(layers)]
    assert [i for i, layer in enumerate(kinds) if layer.attn is not None] \
        == full
    assert all((layer.attn is None) != (layer.gdn is None)
               for layer in kinds)


def test_children_are_registered_under_the_names_the_regions_read():
    net = _net()
    linear = net.layers._children["layer0"]
    full = net.layers._children["layer3"]
    assert set(linear.gdn._children) == {"in_proj", "conv", "scan",
                                         "gate_norm", "out_proj"}
    assert set(full.attn._children) == {"q_proj", "k_proj", "v_proj",
                                        "q_norm", "k_norm", "o_proj"}
    assert set(full.moe._children) == {"router", "experts", "shared",
                                       "shared_gate"}
    assert {"attn_norm", "gdn", "ffn_norm", "moe"} == set(linear._children)
    assert {"attn_norm", "attn", "ffn_norm", "moe"} == set(full._children)
    assert {"embed", "layers", "norm", "lm_head"} <= set(net._children)
    # no bias anywhere, and the softmax router has no choice bias
    assert not [n for n in net.collect_params().keys() if n.endswith("bias")
                and not n.endswith("dt_bias")]


def test_zero_centred_norm_scales_by_one_plus_its_weight():
    x = np.random.RandomState(0).randn(3, 5, 8).astype(np.float32)
    norm = RMSNorm(8, eps=1e-6, zero_centered=True)
    norm.initialize()
    assert np.array_equal(norm.weight.data().asnumpy(), np.zeros(8))
    plain = x / np.sqrt((x * x).mean(-1, keepdims=True) + 1e-6)
    np.testing.assert_allclose(norm(mx.nd.array(x)).asnumpy(), plain,
                               rtol=1e-5)
    w = np.linspace(-0.5, 0.5, 8).astype(np.float32)
    norm.weight.set_data(mx.nd.array(w))
    np.testing.assert_allclose(norm(mx.nd.array(x)).asnumpy(),
                               plain * (1 + w), rtol=1e-5)
    ones = RMSNorm(8, eps=1e-6)         # the form every other caller has
    ones.initialize()
    np.testing.assert_allclose(ones(mx.nd.array(x)).asnumpy(), plain,
                               rtol=1e-5)


def _softplus(x):
    return np.logaddexp(x, 0.0)


def _sigmoid(x):
    return 1.0 / (1.0 + np.exp(-x))


def _silu(x):
    return x * _sigmoid(x)


def test_gated_delta_net_is_its_equations():
    """The mixer against the published equations in NumPy float64, the
    recurrence token by token."""
    rs = np.random.RandomState(0)
    gdn = qwen3_next.GatedDeltaNet(24, eps=1e-6, prefix="g_", **LIN)
    gdn.initialize(mx.init.Normal(0.3))
    gdn.scan.A_log.set_data(mx.nd.array(rs.uniform(-3, 0, 4)))
    gdn.scan.dt_bias.set_data(mx.nd.array(rs.uniform(0, 1, 4)))
    gdn.gate_norm.weight.set_data(mx.nd.array(rs.uniform(0.5, 1.5, 16)))
    x = rs.randn(2, 70, 24)
    got = gdn(mx.nd.array(x, dtype="float32")).asnumpy()

    p = {n.split("g_", 1)[1]: v.data().asnumpy().astype(np.float64)
         for n, v in gdn.collect_params().items()}
    hk, hv, dk, dv = 2, 4, 16, 16
    qkvz = x @ p["in_qkvz_weight"].T
    ba = x @ p["in_ba_weight"].T
    conv_in = qkvz[..., :2 * hk * dk + hv * dv]
    padded = np.pad(conv_in, ((0, 0), (3, 0), (0, 0)))
    conv = sum(padded[:, j:j + 70] * p["conv_weight"][:, j]
               for j in range(4))
    qkv = _silu(conv)
    z = qkvz[..., 2 * hk * dk + hv * dv:].reshape(2, 70, hv, dv)
    q = qkv[..., :hk * dk].reshape(2, 70, hk, dk)
    k = qkv[..., hk * dk:2 * hk * dk].reshape(2, 70, hk, dk)
    v = qkv[..., 2 * hk * dk:].reshape(2, 70, hv, dv)
    q = q / np.sqrt((q * q).sum(-1, keepdims=True) + 1e-6) / np.sqrt(dk)
    k = k / np.sqrt((k * k).sum(-1, keepdims=True) + 1e-6)
    q, k = np.repeat(q, 2, axis=2), np.repeat(k, 2, axis=2)
    beta = _sigmoid(ba[..., :hv])
    g = -np.exp(p["scan_A_log"]) * _softplus(ba[..., hv:]
                                             + p["scan_dt_bias"])
    out = np.zeros((2, 70, hv, dv))
    state = np.zeros((2, hv, dk, dv))
    for t in range(70):
        state = state * np.exp(g[:, t])[..., None, None]
        seen = np.einsum("bhkv,bhk->bhv", state, k[:, t])
        state = state + np.einsum(
            "bhk,bhv->bhkv", k[:, t], beta[:, t][..., None]
            * (v[:, t] - seen))
        out[:, t] = np.einsum("bhkv,bhk->bhv", state, q[:, t])
    out = out / np.sqrt((out * out).mean(-1, keepdims=True) + 1e-6) \
        * p["gate_norm_weight"] * _silu(z)
    want = out.reshape(2, 70, hv * dv) @ p["out_weight"].T
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-4 * np.abs(want).max())


def test_gated_attention_is_its_equations():
    rs = np.random.RandomState(1)
    att = qwen3_next.GatedAttention(24, eps=1e-6, prefix="a_", **ATT)
    att.initialize(mx.init.Normal(0.3))
    att.q_norm.weight.set_data(mx.nd.array(rs.uniform(-0.3, 0.3, 32)))
    att.k_norm.weight.set_data(mx.nd.array(rs.uniform(-0.3, 0.3, 32)))
    x = rs.randn(2, 12, 24)
    got = att(mx.nd.array(x, dtype="float32")).asnumpy()

    p = {n.split("a_", 1)[1]: v.data().asnumpy().astype(np.float64)
         for n, v in att.collect_params().items()}
    h, kv, d, rot = 4, 2, 32, 8

    def norm(t, w):
        return t / np.sqrt((t * t).mean(-1, keepdims=True) + 1e-6) * (1 + w)

    def rope(t):        # (b, heads, s, d): the first `rot` dims, half-split
        half = rot // 2
        freq = 1e7 ** (-np.arange(half) / half)
        angle = np.arange(t.shape[2])[:, None] * freq[None]
        a, b = t[..., :half], t[..., half:rot]
        return np.concatenate([a * np.cos(angle) - b * np.sin(angle),
                               a * np.sin(angle) + b * np.cos(angle),
                               t[..., rot:]], -1)

    qg = (x @ p["q_weight"].T).reshape(2, 12, h, 2 * d)
    gate = qg[..., d:].reshape(2, 12, h * d)
    q = rope(norm(qg[..., :d], p["q_norm_weight"]).transpose(0, 2, 1, 3))
    k = rope(norm((x @ p["k_weight"].T).reshape(2, 12, kv, d),
                  p["k_norm_weight"]).transpose(0, 2, 1, 3))
    v = (x @ p["v_weight"].T).reshape(2, 12, kv, d).transpose(0, 2, 1, 3)
    k, v = np.repeat(k, h // kv, axis=1), np.repeat(v, h // kv, axis=1)
    scores = np.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
    scores = np.where(np.tril(np.ones((12, 12), bool)), scores, -np.inf)
    prob = np.exp(scores - scores.max(-1, keepdims=True))
    prob /= prob.sum(-1, keepdims=True)
    ctx = np.einsum("bhqk,bhkd->bhqd", prob, v).transpose(0, 2, 1, 3) \
        .reshape(2, 12, h * d)
    want = (ctx * _sigmoid(gate)) @ p["o_weight"].T
    np.testing.assert_allclose(got, want, rtol=2e-4,
                               atol=2e-4 * np.abs(want).max())


@pytest.fixture(scope="module")
def compiled_step():
    telemetry.REGISTRY.reset()
    net = _net()
    step = _step(net)
    tok = _tokens()
    text = step.lowered(mx.nd.array(tok, dtype="int32"),
                        mx.nd.array(tok, dtype="int32")).compile().as_text()
    return net, step, tok, text


@pytest.mark.parametrize("scope", [
    "qwen3next/layers/layer0/gdn/in_proj", "qwen3next/layers/layer1/gdn/conv",
    "qwen3next/layers/layer2/gdn/scan", "gdn_scan",
    "qwen3next/layers/layer0/gdn/gate_norm",
    "qwen3next/layers/layer0/gdn/out_proj",
    "qwen3next/layers/layer3/attn/q_proj",
    "qwen3next/layers/layer3/attn/k_norm",
    "qwen3next/layers/layer3/attn/o_proj", "attention",
    "qwen3next/layers/layer3/moe/router",
    "qwen3next/layers/layer0/moe/experts", "dispatch", "grouped", "combine",
    "qwen3next/layers/layer1/moe/shared",
    "qwen3next/layers/layer1/moe/shared_gate",
    "qwen3next/layers/layer2/ffn_norm", "qwen3next/norm",
    "qwen3next/lm_head", "loss", "optimizer"])
def test_scopes_reach_the_compiled_step_forward_and_backward(compiled_step,
                                                             scope):
    from perfbench.scopes import scope_path
    _, _, _, text = compiled_step
    names = re.findall(r'op_name="([^"]*)"', text)
    parts = scope.split("/")

    def carries(name):
        path = scope_path(name)
        return any(path[i:i + len(parts)] == parts
                   for i in range(len(path)))
    hits = [n for n in names if carries(n)]
    assert hits, scope
    if scope != "optimizer":
        assert any("transpose(" in n for n in hits), scope


def test_the_region_file_names_every_scope_of_the_step(compiled_step):
    """No new unattributed time: every instruction of the lowered step that
    carries a scope of the model, the loss or the optimizer falls in a
    region of perfbench/regions/qwen3_next_zoo.json, and each region the
    new metrics read has instructions."""
    from perfbench import scopes
    _, _, _, text = compiled_step
    rules = scopes.load_regions("qwen3_next_zoo")
    names = set(re.findall(r'op_name="([^"]*)"', text))
    model = [n for n in names if {"qwen3next", "loss", "optimizer"}
             & set(scopes.scope_path(n))]
    assert model
    unnamed = [n for n in model if scopes.region_of_path(n, rules) is None]
    assert not unnamed, unnamed[:5]
    found = {scopes.region_of_path(n, rules) for n in model}
    assert {"gdn_scan", "gdn_proj", "attn_proj", "attention", "moe_route",
            "moe_experts", "encoder_dense", "head_loss", "optimizer",
            "other"} <= found


def test_a_traced_forward_counts_the_scans_chunks_by_layer(compiled_step):
    net, step, tok, _ = compiled_step
    telemetry.REGISTRY.reset()
    losses = step.run(mx.nd.array(tok, dtype="int32"),
                      mx.nd.array(tok, dtype="int32")).asnumpy()
    assert np.isfinite(losses).all()
    by_layer = {dict(m.labels)["layer"]: m.value
                for m in telemetry.REGISTRY.collect()
                if m.name == "mxnet_gdn_chunks_total"}
    assert set(by_layer) == {f"qwen3next/layers/layer{i}/gdn/scan"
                             for i in range(3)}
    # batch 2 x 4 value heads x 2 chunks of 64 for 96 positions, once a
    # trace of the forward; the three linear layers alike
    per_trace = 2 * 4 * 2
    assert len(set(by_layer.values())) == 1
    assert by_layer["qwen3next/layers/layer0/gdn/scan"] % per_trace == 0
    # the imperative forward outside any trace counts nothing
    before = dict(by_layer)
    net(mx.nd.array(tok[0], dtype="int32"))
    after = {dict(m.labels)["layer"]: m.value
             for m in telemetry.REGISTRY.collect()
             if m.name == "mxnet_gdn_chunks_total"}
    assert after == before


def test_a_bfloat16_multi_precision_step_moves_every_master():
    import ml_dtypes
    net = _net(dtype=ml_dtypes.bfloat16)
    before = {n: p.data().asnumpy().astype("float32")
              for n, p in net.collect_params().items()}
    step = _step(net, multi_precision=True)
    tok = _tokens(seed=1)
    with jax.default_matmul_precision("default"):
        losses = step.run(mx.nd.array(tok, dtype="int32"),
                          mx.nd.array(tok, dtype="int32")).asnumpy()
    assert np.isfinite(losses).all()
    state = step.optimizer_state()
    assert set(state) == set(before)
    for name in before:
        master = state[name]["weight"]
        assert str(master.dtype) == "float32"
        assert not np.array_equal(master.asnumpy(), before[name]), name
