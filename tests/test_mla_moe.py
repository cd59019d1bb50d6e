"""The MLA + dropless-MoE decoder of the zoo (gluon/model_zoo/mla_moe.py):
interleaved RoPE, the names its blocks put into the compiled step forward
and backward, the counters the routed layers report through the step, and a
frozen router bias inside a multi_precision TrainStep."""

import numpy as np
import pytest

import jax

import mxnet_tpu as mx
from mxnet_tpu import parallel, telemetry
from mxnet_tpu.gluon.model_zoo import mla_moe
from mxnet_tpu.gluon.model_zoo.llama import _rope

ATT = dict(heads=4, qk_nope=16, qk_rope=8, v_head=16, kv_lora_rank=32,
           rope_base=1e6)
MOE = dict(hidden_size=32, num_experts=16, num_experts_per_token=3,
           experts_held=(4, 8), num_shared_experts=2,
           routed_scaling_factor=2.448)


def _complex_rope(x, base):
    """Pairs (2i, 2i+1) as complex numbers turned by pos * base^(-2i/d),
    written out [real parts | imaginary parts]."""
    d = x.shape[-1]
    freq = base ** (-np.arange(0, d, 2) / d)
    turn = np.exp(1j * np.arange(x.shape[-2])[:, None] * freq[None])
    z = (x[..., 0::2] + 1j * x[..., 1::2]) * turn
    return np.concatenate([z.real, z.imag], axis=-1)


def test_interleaved_rope_is_the_complex_rotation():
    x = np.random.RandomState(0).randn(2, 3, 10, 8)
    got = _rope(mx.nd, mx.nd.array(x, dtype="float64"), 1e6,
                interleaved=True).asnumpy()
    np.testing.assert_allclose(got, _complex_rope(x, 1e6), atol=1e-6)


def test_rope_turns_only_the_slice_it_is_given_and_takes_its_base():
    x = np.random.RandomState(1).randn(1, 2, 6, 24)
    got = _rope(mx.nd, mx.nd.array(x, dtype="float64"), 1e4, rotate=(16, 24),
                interleaved=True).asnumpy()
    np.testing.assert_allclose(got[..., :16], x[..., :16], atol=1e-12)
    np.testing.assert_allclose(got[..., 16:],
                               _complex_rope(x[..., 16:], 1e4), atol=1e-6)
    other = _rope(mx.nd, mx.nd.array(x, dtype="float64"), 5e5,
                  rotate=(16, 24), interleaved=True).asnumpy()
    assert np.abs(other - got).max() > 1e-3
    # the rotate-half form the llama blocks use is another pairing
    half = _rope(mx.nd, mx.nd.array(x, dtype="float64"), 1e4,
                 rotate=(16, 24)).asnumpy()
    assert np.abs(half - got).max() > 1e-3


def _net(dtype=None):
    net = mla_moe.MLAMoEModel(256, 3, 64, 96, ATT, MOE, prefix="mlamoe_")
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


def _tokens(steps=2, batch=2, seq=32, seed=0):
    return np.random.RandomState(seed).randint(
        0, 256, (steps, batch, seq)).astype(np.int32)


def test_children_are_registered_under_the_names_the_regions_read():
    net = _net()
    layer = net.layers._children["layer1"]
    assert set(layer.mla._children) == {"q_proj", "kv_a_proj", "kv_a_norm",
                                        "kv_b_proj", "o_proj"}
    assert set(layer.moe._children) == {"router", "experts", "shared"}
    assert net.layers._children["layer0"].mlp is not None
    assert {"embed", "layers", "norm", "lm_head"} <= set(net._children)


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
    "mlamoe/layers/layer1/mla/q_proj", "mlamoe/layers/layer1/mla/kv_b_proj",
    "attention", "mlamoe/layers/layer2/moe/router",
    "mlamoe/layers/layer2/moe/experts", "dispatch", "grouped", "combine",
    "mlamoe/layers/layer2/moe/shared", "mlamoe/layers/layer0/mlp",
    "mlamoe/lm_head", "loss", "optimizer"])
def test_scopes_reach_the_compiled_step_forward_and_backward(compiled_step,
                                                             scope):
    import re
    _, _, _, text = compiled_step
    names = re.findall(r'op_name="([^"]*)"', text)
    parts = scope.split("/")

    def carries(name):
        from perfbench.scopes import scope_path
        path = scope_path(name)
        return any(path[i:i + len(parts)] == parts
                   for i in range(len(path)))
    hits = [n for n in names if carries(n)]
    assert hits, scope
    if scope != "optimizer":
        assert any("transpose(" in n for n in hits), scope


def test_step_returns_counters_and_banks_them_when_losses_are_fetched(
        compiled_step):
    net, step, tok, _ = compiled_step
    telemetry.REGISTRY.reset()
    losses = step.run(mx.nd.array(tok, dtype="int32"),
                      mx.nd.array(tok, dtype="int32"))
    layer = {"layer": "mlamoe/layers/layer1/moe"}
    assert telemetry.REGISTRY.get("mxnet_moe_pairs_total", layer) is None \
        or telemetry.REGISTRY.get("mxnet_moe_pairs_total", layer).value == 0
    assert np.isfinite(losses.asnumpy()).all()
    pairs = telemetry.REGISTRY.get("mxnet_moe_pairs_total", layer).value
    tokens = telemetry.REGISTRY.get("mxnet_moe_tokens_total").value
    fullest = telemetry.REGISTRY.get("mxnet_moe_expert_tokens_max",
                                     layer).value
    assert tokens == 2 * 2 * 64          # steps x routed layers x tokens
    # 8 of 16 experts held, top 3: about 1.5 pairs a token, never over 3
    assert 0 < pairs <= 2 * 64 * 3
    assert pairs / (2 * 8) <= fullest <= 64
    # one window of the sorted pair buffer a layer and step: 64 tokens are
    # less than a window, and the loop's trip count follows the pairs
    assert telemetry.REGISTRY.get("mxnet_moe_windows_total",
                                  layer).value == 2
    losses.asnumpy()                     # a second fetch banks nothing more
    assert telemetry.REGISTRY.get("mxnet_moe_pairs_total",
                                  layer).value == pairs


def test_report_counter_outside_a_step_does_nothing():
    telemetry.REGISTRY.reset()
    parallel.report_counter("mxnet_moe_tokens_total", 5)
    banked = telemetry.REGISTRY.get("mxnet_moe_tokens_total")
    assert banked is None or banked.value == 0


def test_reports_under_remat_warn_once_and_bank_nothing():
    """A value reported from inside a checkpointed forward cannot leave it:
    the step says so, once a name, and the counters stay where they are."""
    telemetry.REGISTRY.reset()
    parallel._warned_under_remat.clear()
    net = _net()
    opt = mx.optimizer.Adam(learning_rate=1e-3)
    mesh = parallel.make_mesh(shape=(1,), axis_names=("dp",),
                              devices=jax.devices()[:1])
    step = parallel.TrainStep(net, _loss, opt, mesh=mesh, remat=True)
    tok = mx.nd.array(_tokens(steps=1), dtype="int32")
    with pytest.warns(RuntimeWarning, match="under\\s+remat") as caught:
        losses = step.run(tok, tok).asnumpy()
    assert np.isfinite(losses).all()
    named = [str(w.message).split("'")[1] for w in caught
             if "report_counter" in str(w.message)]
    assert sorted(named) == ["mxnet_moe_expert_tokens_max",
                             "mxnet_moe_pairs_total",
                             "mxnet_moe_tokens_total",
                             "mxnet_moe_windows_total"]
    banked = telemetry.REGISTRY.get("mxnet_moe_tokens_total")
    assert banked is None or banked.value == 0


def test_frozen_router_bias_inside_a_multi_precision_step():
    import ml_dtypes
    net = _net(ml_dtypes.bfloat16)
    before = {n: p.data().asnumpy().astype("float32")
              for n, p in net.collect_params().items()}
    step = _step(net, multi_precision=True)
    tok = _tokens(seed=1)
    with jax.default_matmul_precision("default"):
        losses = step.run(mx.nd.array(tok, dtype="int32"),
                          mx.nd.array(tok, dtype="int32")).asnumpy()
    assert np.isfinite(losses).all()
    state = step.optimizer_state()
    for name, p in net.collect_params().items():
        after = p.data().asnumpy().astype("float32")
        if name.endswith("router_bias"):
            assert name not in state
            assert np.array_equal(after, before[name])
        else:
            # the float32 master moved (the bfloat16 copy of a leaf whose
            # gradient is tiny at these weights, the router's or the query
            # projection's, may round back to where it was)
            master = state[name]["weight"]
            assert str(master.dtype) == "float32"
            assert not np.array_equal(master.asnumpy(), before[name]), name
