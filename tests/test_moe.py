"""SparseMoE (gluon.contrib.moe) — dense-dispatch MoE + expert parallelism.

Reference: ABSENT upstream (SURVEY §2.4 "Expert parallel / MoE: ABSENT") —
validates the new GShard/Switch-style design: routing correctness vs a
per-token numpy oracle, capacity semantics, gradient flow, hybridize parity,
and an expert-parallel TrainStep on a dp×ep mesh matching single-device
numerics.
"""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd
from mxnet_tpu.gluon.contrib import SparseMoE


def _make(units=8, hidden=16, E=4, k=2, cf=8.0, seed=0):
    moe = SparseMoE(units, hidden, E, num_experts_per_token=k,
                    capacity_factor=cf)
    mx.random.seed(seed)
    moe.initialize(mx.init.Xavier())
    return moe


def _numpy_oracle(moe, x):
    """Per-token reference: route each token to its top-k experts (no
    capacity pressure when cf is large), run the expert MLPs densely."""
    import scipy.special as sp
    g = moe.gate_weight.data().asnumpy()
    w1 = moe.expert_w1.data().asnumpy()
    b1 = moe.expert_b1.data().asnumpy()
    w2 = moe.expert_w2.data().asnumpy()
    b2 = moe.expert_b2.data().asnumpy()
    xf = x.reshape(-1, x.shape[-1])
    logits = xf @ g
    probs = sp.softmax(logits, axis=-1)
    k = moe._k
    out = np.zeros_like(xf)
    for n in range(xf.shape[0]):
        top = np.argsort(-probs[n])[:k]
        # Switch (k=1): raw router prob; GShard (k>1): normalized over top-k
        gates = probs[n][top] if k == 1 \
            else probs[n][top] / probs[n][top].sum()
        for gi, e in zip(gates, top):
            h = xf[n] @ w1[e] + b1[e]
            h = 0.5 * h * (1 + sp.erf(h / np.sqrt(2)))  # exact gelu
            out[n] += gi * (h @ w2[e] + b2[e])
    return out.reshape(x.shape)


def test_moe_matches_per_token_oracle():
    moe = _make()
    x = np.random.RandomState(1).randn(6, 3, 8).astype(np.float32)
    y, aux = moe(mx.nd.array(x))
    assert y.shape == x.shape
    assert aux.shape == ()
    np.testing.assert_allclose(y.asnumpy(), _numpy_oracle(moe, x),
                               rtol=2e-4, atol=2e-5)
    # balanced-ish random routing: aux stays near its minimum of 1.0
    assert 0.5 < float(aux.asnumpy()) < float(moe._E)


def test_moe_capacity_drops_overflow_tokens():
    """capacity_factor small enough that an expert overflows: dropped tokens
    contribute zero output (residual semantics), none crash."""
    units, E = 4, 2
    moe = SparseMoE(units, 8, E, num_experts_per_token=1,
                    capacity_factor=0.25)
    mx.random.seed(3)
    moe.initialize(mx.init.Xavier())
    N = 16
    x = np.random.RandomState(2).randn(N, units).astype(np.float32)
    y, _ = moe(mx.nd.array(x))
    # capacity C = ceil(1*16/2*0.25) = 2 slots/expert → ≤ 4 tokens served
    served = (np.abs(y.asnumpy()).sum(axis=1) > 1e-9).sum()
    assert served <= 2 * E


def test_moe_gradients_flow():
    moe = _make(seed=5)
    x = mx.nd.array(np.random.RandomState(4).randn(8, 8).astype(np.float32))
    with autograd.record():
        y, aux = moe(x)
        loss = y.square().mean() + 0.01 * aux
    loss.backward()
    for name, p in moe.collect_params().items():
        g = p.grad().asnumpy()
        assert np.isfinite(g).all(), name
    # router must receive gradient through both gates and aux loss
    assert np.abs(moe.gate_weight.grad().asnumpy()).max() > 0


@pytest.mark.parametrize("k", [1, 2])
def test_moe_router_gets_task_gradient_imperatively(k):
    """The combine-weight path must carry task-loss gradient to the router
    WITHOUT the aux term, in imperative mode (topk outputs are detached on
    the tape; gates are re-gathered from probs differentiably)."""
    moe = SparseMoE(8, 16, 4, num_experts_per_token=k, capacity_factor=8.0)
    mx.random.seed(9)
    moe.initialize(mx.init.Xavier())
    x = mx.nd.array(np.random.RandomState(10).randn(8, 8).astype(np.float32))
    with autograd.record():
        y, _ = moe(x)
        loss = y.square().mean()      # no aux — pure task loss
    loss.backward()
    assert np.abs(moe.gate_weight.grad().asnumpy()).max() > 0


def test_moe_hybridize_parity():
    moe = _make(seed=7)
    x = mx.nd.array(np.random.RandomState(6).randn(4, 2, 8).astype(np.float32))
    y_imp, aux_imp = moe(x)
    moe.hybridize()
    y_hyb, aux_hyb = moe(x)
    np.testing.assert_allclose(y_imp.asnumpy(), y_hyb.asnumpy(),
                               rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(aux_imp.asnumpy(), aux_hyb.asnumpy(),
                               rtol=1e-5)


def test_moe_expert_parallel_trainstep():
    """dp×ep mesh: expert weights sharded over 'ep', numerics match the
    single-device run step-for-step."""
    import jax
    from mxnet_tpu.parallel import DeviceMesh, TrainStep
    from mxnet_tpu.gluon import nn, HybridBlock

    units, hidden, E = 8, 16, 4

    class MoENet(HybridBlock):
        def __init__(self, **kw):
            super().__init__(**kw)
            with self.name_scope():
                self.moe = SparseMoE(units, hidden, E,
                                     num_experts_per_token=2,
                                     capacity_factor=8.0)
                self.head = nn.Dense(2, flatten=False, in_units=units)

        def hybrid_forward(self, F, x):
            y, aux = self.moe(x)
            self._aux = aux
            return self.head(y)

    def loss_fn(out, label):
        from mxnet_tpu.gluon import loss as gloss
        return gloss.L2Loss()(out, label)

    rs = np.random.RandomState(8)
    x = rs.randn(16, units).astype(np.float32)
    lbl = rs.randn(16, 2).astype(np.float32)

    def run(mesh):
        mx.random.seed(11)
        net = MoENet()
        net.initialize(mx.init.Xavier())
        step = TrainStep(net, loss_fn, "sgd", {"learning_rate": 0.1},
                         mesh=mesh)
        losses = [float(step(mx.nd.array(x), mx.nd.array(lbl)).asnumpy())
                  for _ in range(3)]
        return losses

    single = run(DeviceMesh(devices=jax.devices()[:1], axis_names=("dp",)))
    mesh = DeviceMesh(shape=(2, 4), axis_names=("dp", "ep"))
    sharded = run(mesh)
    np.testing.assert_allclose(single, sharded, rtol=1e-4, atol=1e-5)
    assert sharded[-1] < sharded[0]


# -- DroplessMoE: sigmoid router over all experts, experts held, no drop ------

from mxnet_tpu.gluon.contrib.moe import DroplessMoE  # noqa: E402


def _route(x, w, b, **kw):
    weights, experts = mx.nd.contrib.moe_router(
        mx.nd.array(x), mx.nd.array(w), mx.nd.array(b), **kw)
    return weights.asnumpy(), experts.asnumpy()


def test_router_sigmoid_bias_for_choice_not_weight_normalised_scaled():
    r = np.random.RandomState(0)
    x, w = r.randn(6, 8).astype("float32"), r.randn(5, 8).astype("float32")
    b = np.array([0, 0, 10.0, 0, -10.0], "float32")
    s = 1.0 / (1.0 + np.exp(-(x @ w.T)))
    weights, experts = _route(x, w, b, k=2, scale=2.5, normalize=True)
    assert experts.dtype == np.int32 and weights.dtype == np.float32
    # the bias decides the choice: expert 2 always first, expert 4 never
    assert (experts[:, 0] == 2).all() and (experts != 4).all()
    second = np.argsort(-np.where(np.arange(5) == 2, -1, s + b), axis=1)[:, 0]
    assert (experts[:, 1] == second).all()
    # ... and stays out of the weights: sigmoid scores, normalised, scaled
    picked = np.take_along_axis(s, experts, axis=1)
    np.testing.assert_allclose(
        weights, 2.5 * picked / picked.sum(1, keepdims=True), rtol=1e-5)
    raw, _ = _route(x, w, b, k=2, scale=1.0, normalize=False)
    np.testing.assert_allclose(raw, picked, rtol=1e-5)


def test_router_tie_goes_to_the_lower_expert():
    x = np.ones((3, 4), "float32")
    w = np.zeros((6, 4), "float32")         # every score sigmoid(0)
    _, experts = _route(x, w, np.zeros(6, "float32"), k=3)
    assert (experts == np.array([0, 1, 2])).all()
    _, experts = _route(x, w, np.array([0, 1, 0, 1, 0, 0], "float32"), k=3)
    assert (experts == np.array([1, 3, 0])).all()


def _dropless(held, E=8, k=3, units=8, hidden=16, shared=1, seed=0):
    moe = DroplessMoE(units, hidden, E, k, experts_held=held,
                      num_shared_experts=shared, routed_scaling_factor=2.0)
    mx.random.seed(seed)
    moe.initialize(mx.init.Normal(0.5))
    return moe


def _dropless_oracle(moe, x, held):
    """Token by token, expert by expert, in numpy."""
    p = {k[len(moe.prefix):]: v.data().asnumpy()
         for k, v in moe.collect_params().items()}
    silu = lambda a: a / (1.0 + np.exp(-a))             # noqa: E731
    s = 1.0 / (1.0 + np.exp(-(x @ p["router_weight"].T)))
    top = np.argsort(-(s + p["router_bias"]), axis=1, kind="stable")[:, :3]
    out = np.zeros_like(x)
    for n in range(x.shape[0]):
        w = s[n, top[n]] / (s[n, top[n]].sum() + 1e-20) * 2.0
        for j, e in enumerate(top[n]):
            if held[0] <= e < held[0] + held[1]:
                l = e - held[0]
                h = silu(x[n] @ p["experts_gate"][l]) \
                    * (x[n] @ p["experts_up"][l])
                out[n] += w[j] * (h @ p["experts_down"][l])
    sh = silu(x @ p["shared_gate_weight"].T) * (x @ p["shared_up_weight"].T)
    return out + sh @ p["shared_down_weight"].T


@pytest.mark.parametrize("held", [(0, 8), (2, 3), (7, 1)])
def test_dropless_moe_matches_per_token_oracle(held):
    moe = _dropless(held)
    x = np.random.RandomState(1).randn(2, 12, 8).astype("float32")
    y = moe(mx.nd.array(x)).asnumpy()
    ref = _dropless_oracle(moe, x.reshape(-1, 8), held).reshape(x.shape)
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-5)


def test_dropless_moe_drops_no_token_under_a_planted_imbalance():
    """Every token sent to ONE held expert (a router bias no score can
    beat): 32 tokens on an expert whose even share is 32 * 3 / 8 = 12, all
    served, and counted."""
    moe = _dropless((2, 3))
    bias = np.zeros(8, "float32")
    bias[3] = 100.0
    moe.router.bias.set_data(mx.nd.array(bias))
    x = np.random.RandomState(2).randn(32, 8).astype("float32")
    xn = mx.nd.array(x)
    weights, experts = moe.router(xn)
    y, tokens, _windows = moe.experts(xn, weights, experts)
    assert tokens.asnumpy().tolist()[1] == 32
    assert (experts.asnumpy()[:, 0] == 3).all()
    ref = _dropless_oracle(moe, x, (2, 3))
    np.testing.assert_allclose(moe(xn).asnumpy(), ref, rtol=1e-4, atol=1e-5)
    # every token got its routed part: none is the shared part alone
    assert (np.abs(y.asnumpy()).sum(axis=1) > 0).all()


def test_dropless_moe_gradients_and_frozen_bias():
    moe = _dropless((0, 8))
    x = mx.nd.array(np.random.RandomState(3).randn(16, 8).astype("float32"))
    x.attach_grad()
    with autograd.record():
        loss = (moe(x) ** 2).sum()
    loss.backward()
    assert moe.router.bias.grad_req == "null"
    for name, p in moe.collect_params().items():
        if p.grad_req != "null":
            g = p.grad().asnumpy()
            assert np.isfinite(g).all() and np.abs(g).sum() > 0, name
    assert np.abs(x.grad.asnumpy()).sum() > 0
    # against finite differences through the whole layer, on the input
    eps, xv = 1e-3, x.asnumpy()
    d = np.zeros_like(xv)
    d[5, 2] = eps
    up = (moe(mx.nd.array(xv + d)) ** 2).sum().asscalar()
    dn = (moe(mx.nd.array(xv - d)) ** 2).sum().asscalar()
    assert x.grad.asnumpy()[5, 2] == pytest.approx((up - dn) / (2 * eps),
                                                   rel=2e-2)


def test_dropless_moe_shares_its_experts_over_the_ep_axis():
    moe = _dropless((0, 8))
    for p in (moe.experts.gate, moe.experts.up, moe.experts.down):
        assert p.sharding == ("ep", None, None)


def _plant(n, k, held, absent, fill):
    """``experts`` (n, k) int32 over ``held + absent`` experts: the first
    ``fill[e]`` pairs in pair order go to held expert ``e`` (ids ``4 …``),
    the rest to absent ones (ids ``0 … 3``, ``4 + held …``) in turn."""
    ids = [4 + e for e, c in enumerate(fill) for _ in range(c)]
    away = [e for e in range(4 + held + absent) if not 4 <= e < 4 + held]
    ids += [away[p % len(away)] for p in range(n * k - len(ids))]
    return np.asarray(ids, np.int32).reshape(n, k)


# 64 tokens x 3 choices = 192 pairs, windows of 16 rows, 3 experts held
_LOADS = {
    "even_router": None,                    # the router's own choices
    "all_on_one_held_expert": None,         # ... with a bias on one expert
    "no_live_pair": [0, 0, 0],
    "usual_share": [5, 0, 7],               # one window, one expert empty
    "exactly_one_window": [4, 6, 6],
    "one_window_and_one_pair": [4, 6, 7],
    "an_expert_straddles_two_edges": [3, 40, 2],
    "every_pair_live": [64, 60, 68],        # 12 windows, held >= k
}


@pytest.mark.parametrize("load", list(_LOADS))
def test_moe_experts_equal_masked_dense_products(load, monkeypatch):
    """The op (the sorted pair buffer walked in windows, the trip count the
    live pairs') against every held expert run densely over ALL tokens
    under its weight: ``y``, ``tokens``, the windows walked and every
    gradient, in float32, at windows of 16 rows and planted loads from no
    live pair to all N*k of them.  What a grouped product never writes on
    the chip (rows past its groups) is planted with NaN."""
    import jax
    import jax.numpy as jnp
    from mxnet_tpu.ops import moe
    from mxnet_tpu.ops.moe import _moe_experts, _moe_router
    rows, n, k, held, first = 16, 64, 3, 3, 4
    monkeypatch.setattr(moe, "_window_rows", lambda n, k: rows)

    def ragged_dot_nan_past_the_groups(lhs, rhs, group_sizes):
        out = jax.lax.ragged_dot(lhs, rhs, group_sizes)
        written = jnp.arange(out.shape[0]) < group_sizes.sum()
        return jnp.where(written[:, None], out, jnp.nan)

    monkeypatch.setattr(moe, "_ragged_dot", ragged_dot_nan_past_the_groups)
    r = np.random.RandomState(4)
    x = jnp.asarray(r.randn(n, 8), jnp.float32)
    rw = jnp.asarray(r.randn(16, 8), jnp.float32)
    rb = jnp.zeros(16, jnp.float32).at[5].set(
        100.0 if load == "all_on_one_held_expert" else 0.0)
    gate, up = (jnp.asarray(r.randn(held, 8, 12), jnp.float32) for _ in "gu")
    down = jnp.asarray(r.randn(held, 12, 8), jnp.float32)
    weights, experts = _moe_router(x, rw, rb, k=k, scale=2.0)
    if _LOADS[load] is not None:
        experts = jnp.asarray(_plant(n, k, held, 9, _LOADS[load]))
    cot = jnp.asarray(r.randn(n, 8), jnp.float32)

    def op(x, weights, gate, up, down):
        y, tokens, windows = _moe_experts(x, weights, experts, gate, up,
                                          down, first=first)
        return (y * cot).sum(), (tokens, windows)

    def dense(x, weights, gate, up, down):
        y = jnp.zeros_like(x)
        for e in range(gate.shape[0]):
            w = jnp.where(experts == first + e, weights, 0.0).sum(axis=1)
            h = jax.nn.silu(x @ gate[e]) * (x @ up[e])
            y = y + w[:, None] * (h @ down[e])
        return (y * cot).sum(), None

    args = (x, weights, gate, up, down)
    (got, (tokens, windows)), g_got = jax.value_and_grad(
        op, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
    (want, _), g_want = jax.value_and_grad(
        dense, argnums=(0, 1, 2, 3, 4), has_aux=True)(*args)
    local = np.asarray(experts) - first
    assert np.asarray(tokens).tolist() == [int((local == e).sum())
                                           for e in range(held)]
    if _LOADS[load] is not None:
        assert np.asarray(tokens).tolist() == _LOADS[load]
    if load == "all_on_one_held_expert":
        assert int(tokens[1]) == n
    assert int(windows) == -(-int(tokens.sum()) // rows)
    assert float(got) == pytest.approx(float(want), rel=1e-5, abs=1e-5)
    for a, b in zip(g_got, g_want):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-4,
                                   atol=1e-5)


# -- the softmax score and the gated shared expert (qwen3_next's layer) -------

def _route_softmax(x, w, **kw):
    weights, experts = mx.nd.contrib.moe_router(
        mx.nd.array(x), mx.nd.array(w), None, score="softmax", **kw)
    return weights.asnumpy(), experts.asnumpy()


@pytest.mark.parametrize("k, normalize", [(1, True), (3, True), (3, False),
                                          (10, True)])
def test_router_softmax_against_a_plain_top_k(k, normalize):
    """softmax over ALL experts in float32, the k largest, their weights
    over their sum: against jax.numpy written out, no bias anywhere."""
    import jax
    import jax.numpy as jnp
    r = np.random.RandomState(k)
    x, w = r.randn(40, 16).astype("float32"), r.randn(24, 16).astype(
        "float32")
    weights, experts = _route_softmax(x, w, k=k, normalize=normalize)
    assert experts.dtype == np.int32 and weights.dtype == np.float32
    p = jax.nn.softmax(jnp.einsum(
        "nu,eu->ne", x, w, precision=jax.lax.Precision.HIGHEST), axis=-1)
    picked, chosen = jax.lax.top_k(p, k)
    assert np.array_equal(experts, np.asarray(chosen))
    want = picked / picked.sum(-1, keepdims=True) if normalize else picked
    np.testing.assert_allclose(weights, want, rtol=1e-5)
    if normalize:
        np.testing.assert_allclose(weights.sum(1), 1.0, rtol=1e-5)
    # the probabilities come out in descending order, none negative
    assert (np.diff(weights, axis=1) <= 1e-7).all() and (weights > 0).all()


def test_router_softmax_tie_goes_to_the_lower_expert():
    x = np.ones((3, 4), "float32")
    w = np.zeros((6, 4), "float32")         # every probability 1/6
    weights, experts = _route_softmax(x, w, k=3)
    assert (experts == np.array([0, 1, 2])).all()
    np.testing.assert_allclose(weights, 1.0 / 3, rtol=1e-6)
    w[4] = 0.5                              # one expert ahead, the rest tied
    _, experts = _route_softmax(x, w, k=3)
    assert (experts == np.array([4, 0, 1])).all()


def test_router_refuses_a_score_it_does_not_know():
    with pytest.raises(Exception, match="sigmoid\\|softmax"):
        mx.nd.contrib.moe_router(mx.nd.ones((2, 4)), mx.nd.ones((3, 4)),
                                 None, score="tanh")


def _gated(held, E=8, k=3, units=8, hidden=16, seed=0):
    moe = DroplessMoE(units, hidden, E, k, experts_held=held,
                      num_shared_experts=1, score="softmax",
                      shared_gate=True)
    mx.random.seed(seed)
    moe.initialize(mx.init.Normal(0.5))
    return moe


def _gated_oracle(moe, x, held, k=3):
    """Token by token, expert by expert, in numpy."""
    p = {n[len(moe.prefix):]: v.data().asnumpy()
         for n, v in moe.collect_params().items()}
    silu = lambda a: a / (1.0 + np.exp(-a))             # noqa: E731
    logits = x @ p["router_weight"].T
    s = np.exp(logits - logits.max(1, keepdims=True))
    s /= s.sum(1, keepdims=True)
    top = np.argsort(-s, axis=1, kind="stable")[:, :k]
    out = np.zeros_like(x)
    for n in range(x.shape[0]):
        w = s[n, top[n]] / s[n, top[n]].sum()
        for j, e in enumerate(top[n]):
            if held[0] <= e < held[0] + held[1]:
                l = e - held[0]
                h = silu(x[n] @ p["experts_gate"][l]) \
                    * (x[n] @ p["experts_up"][l])
                out[n] += w[j] * (h @ p["experts_down"][l])
    sh = silu(x @ p["shared_gate_weight"].T) * (x @ p["shared_up_weight"].T)
    gate = 1.0 / (1.0 + np.exp(-(x @ p["shgate_weight"].T)))   # (N, 1)
    return out + gate * (sh @ p["shared_down_weight"].T)


@pytest.mark.parametrize("held", [(0, 8), (2, 3), (7, 1)])
def test_softmax_moe_with_a_gated_shared_expert_matches_the_oracle(held):
    moe = _gated(held)
    assert "router_bias" not in {n[len(moe.prefix):]
                                 for n in moe.collect_params().keys()}
    assert moe.shared_gate.weight.shape == (1, 8)
    x = np.random.RandomState(1).randn(2, 12, 8).astype("float32")
    y = moe(mx.nd.array(x)).asnumpy()
    ref = _gated_oracle(moe, x.reshape(-1, 8), held).reshape(x.shape)
    np.testing.assert_allclose(y, ref, rtol=1e-4, atol=1e-5)


def test_softmax_moe_every_leaf_takes_a_gradient():
    moe = _gated((0, 8))
    x = mx.nd.array(np.random.RandomState(3).randn(16, 8).astype("float32"))
    x.attach_grad()
    with autograd.record():
        loss = (moe(x) ** 2).sum()
    loss.backward()
    for name, p in moe.collect_params().items():
        g = p.grad().asnumpy()
        assert np.isfinite(g).all() and np.abs(g).sum() > 0, name
    # against finite differences of the float64 oracle, on the input
    eps, xv = 1e-6, x.asnumpy().astype(np.float64)
    d = np.zeros_like(xv)
    d[5, 2] = eps
    up = (_gated_oracle(moe, xv + d, (0, 8)) ** 2).sum()
    dn = (_gated_oracle(moe, xv - d, (0, 8)) ** 2).sum()
    assert x.grad.asnumpy()[5, 2] == pytest.approx((up - dn) / (2 * eps),
                                                   rel=1e-3)


@pytest.mark.parametrize("kwargs, match", [
    (dict(score="tanh"), "score"),
    (dict(shared_gate=True), "shared_gate without a shared expert"),
])
def test_dropless_moe_refuses_arguments_that_do_not_fit(kwargs, match):
    from mxnet_tpu.base import MXNetError
    with pytest.raises(MXNetError, match=match):
        DroplessMoE(8, 16, 8, 3, **kwargs)
