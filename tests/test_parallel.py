"""Multi-device data parallelism tests on the 8-virtual-CPU mesh.

Covers split_and_load + per-ctx replicas + kvstore
'device' reduction match single-device numerics, and the fused SPMD
TrainStep (mxnet_tpu.parallel) matches the imperative loop.
"""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, autograd, gluon
from mxnet_tpu.gluon import nn
from mxnet_tpu.test_utils import assert_almost_equal

N_DEV = 8


@pytest.fixture
def ctxs():
    from mxnet_tpu import parallel
    cs = parallel.data_parallel_ctxs()
    assert len(cs) >= N_DEV, "conftest must force 8 cpu devices"
    return cs[:N_DEV]


def _mlp(seed=7):
    mx.random.seed(seed)
    np.random.seed(seed)
    net = nn.HybridSequential(prefix="mlp_")
    with net.name_scope():
        net.add(nn.Dense(16, activation="relu"))
        net.add(nn.Dense(4))
    return net


def _init_net(net, ctx, seed=7):
    mx.random.seed(seed)
    np.random.seed(seed)
    net.initialize(mx.initializer.Xavier(rnd_type="uniform"), ctx=ctx)


def test_split_and_load(ctxs):
    x = nd.array(np.arange(32, dtype="float32").reshape(16, 2))
    parts = gluon.utils.split_and_load(x, ctxs)
    assert len(parts) == N_DEV
    assert all(p.shape == (2, 2) for p in parts)
    for i, p in enumerate(parts):
        assert p.ctx == ctxs[i]
    back = np.concatenate([p.asnumpy() for p in parts])
    assert_almost_equal(back, x.asnumpy())


def test_parameter_replicas(ctxs):
    p = gluon.Parameter("w", shape=(3, 3))
    p.initialize(ctx=ctxs)
    assert len(p.list_data()) == N_DEV
    assert len(p.list_ctx()) == N_DEV
    for c, d in zip(ctxs, p.list_data()):
        assert p.data(c) is d
    # set_data propagates to every replica
    val = np.random.randn(3, 3).astype("float32")
    p.set_data(nd.array(val))
    for d in p.list_data():
        assert_almost_equal(d.asnumpy(), val)


def test_kvstore_device_reduces(ctxs):
    kv = mx.kv.create("device")
    base = nd.zeros((4,))
    kv.init(3, base)
    grads = [nd.array(np.full(4, float(i + 1), "float32"), ctx=c)
             for i, c in enumerate(ctxs)]
    kv.push(3, grads)
    kv.pull(3, grads)
    expect = np.full(4, sum(range(1, N_DEV + 1)), "float32")
    for g, c in zip(grads, ctxs):
        assert_almost_equal(g.asnumpy(), expect)
        assert g.ctx == c


def test_multictx_training_matches_single(ctxs):
    """The defining DP test: 8-replica training == 1-device training."""
    data = np.random.randn(16, 8).astype("float32")
    label = np.random.randn(16, 4).astype("float32")

    def run(ctx_list, steps=3):
        net = _mlp()
        _init_net(net, ctx_list)
        trainer = gluon.Trainer(net.collect_params(), "sgd",
                                {"learning_rate": 0.05}, kvstore="device")
        x_all = nd.array(data)
        y_all = nd.array(label)
        for _ in range(steps):
            xs = gluon.utils.split_and_load(x_all, ctx_list)
            ys = gluon.utils.split_and_load(y_all, ctx_list)
            with autograd.record():
                losses = [((net(x) - y) ** 2).sum() for x, y in zip(xs, ys)]
            for l in losses:
                l.backward()
            trainer.step(len(data))
        return {k: v.data().asnumpy()
                for k, v in net.collect_params().items()}

    single = run([ctxs[0]])
    multi = run(ctxs)
    assert single.keys() == multi.keys()
    for k in single:
        assert_almost_equal(multi[k], single[k], rtol=1e-5, atol=1e-6)


def test_trainstep_matches_imperative():
    """parallel.TrainStep (fused SPMD step) == imperative loop, incl. the
    traced-t Adam bias correction across steps."""
    from mxnet_tpu import parallel
    data = np.random.randn(16, 8).astype("float32")
    label = np.random.randn(16, 4).astype("float32")

    def loss_fn(out, y):
        return ((out - y) ** 2).mean()

    # imperative reference
    net_a = _mlp()
    _init_net(net_a, mx.cpu(0))
    opt_a = mx.optimizer.Adam(learning_rate=0.01)
    trainer = gluon.Trainer(net_a.collect_params(), opt_a, kvstore=None)
    for _ in range(3):
        with autograd.record():
            l = loss_fn(net_a(nd.array(data)), nd.array(label))
        l.backward()
        trainer.step(1)

    # fused step over an 8-device dp mesh
    mesh = parallel.make_mesh(axis_names=("dp",))
    net_b = _mlp()
    _init_net(net_b, mx.cpu(0))
    step = parallel.TrainStep(net_b, loss_fn,
                              mx.optimizer.Adam(learning_rate=0.01),
                              mesh=mesh, donate=False)
    losses = [float(step(data, label).asscalar()) for _ in range(3)]
    assert losses[2] < losses[0]  # it learns

    pa = {k: v.data().asnumpy() for k, v in net_a.collect_params().items()}
    pb = {k: v.data().asnumpy() for k, v in net_b.collect_params().items()}
    for k in pa:
        assert_almost_equal(pb[k], pa[k], rtol=1e-4, atol=1e-5)


def test_allreduce_eager(ctxs):
    from mxnet_tpu import parallel
    mesh = parallel.DeviceMesh(axis_names=("dp",))
    vals = [nd.array(np.full((2, 2), float(i), "float32"), ctx=c)
            for i, c in enumerate(ctxs)]
    out = parallel.allreduce(vals, mesh=mesh)
    expect = np.full((2, 2), sum(range(N_DEV)), "float32")
    for o in out:
        assert_almost_equal(o.asnumpy(), expect)


def test_multictx_adam_replicas_stay_sync(ctxs):
    """code-review r2: shared optimizer counters must advance once per
    logical step, not once per replica (Adam bias correction)."""
    two = ctxs[:2]
    net = _mlp(seed=11)
    _init_net(net, two, seed=11)
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.01}, kvstore="device")
    x = nd.array(np.random.randn(8, 8).astype("float32"))
    y = nd.array(np.random.randn(8, 4).astype("float32"))
    for _ in range(2):
        xs = gluon.utils.split_and_load(x, two)
        ys = gluon.utils.split_and_load(y, two)
        with autograd.record():
            losses = [((net(a) - b) ** 2).sum() for a, b in zip(xs, ys)]
        for l in losses:
            l.backward()
        trainer.step(8)
    assert trainer.optimizer._index_update_count[0] == 2
    for p in net.collect_params().values():
        reps = [d.asnumpy() for d in p.list_data()]
        assert_almost_equal(reps[0], reps[1])


def test_hybridized_multictx_forward(ctxs):
    """code-review r2: hybridized forward with replicas off the default ctx."""
    sub = ctxs[1:3]
    net = _mlp(seed=13)
    _init_net(net, sub, seed=13)
    net.hybridize()
    x = nd.array(np.random.randn(4, 8).astype("float32"), ctx=sub[0])
    out1 = net(x).asnumpy()
    x2 = x.as_in_context(sub[1])
    out2 = net(x2).asnumpy()
    assert_almost_equal(out1, out2, rtol=1e-6)


def test_shared_subgraph_double_backward_raises():
    """code-review r2: freed shared subgraph must raise, not drop grads."""
    x = nd.array([1.0, 2.0, 3.0])
    x.attach_grad()
    with autograd.record():
        y = x * 2
        l1 = y.sum()
        l2 = (y * 3).sum()
    l1.backward()
    with pytest.raises(mx.MXNetError):
        l2.backward()


def test_allreduce_mean(ctxs):
    from mxnet_tpu import parallel
    mesh = parallel.DeviceMesh(axis_names=("dp",))
    vals = [nd.array(np.full((3,), float(i), "float32"), ctx=c)
            for i, c in enumerate(ctxs)]
    out = parallel.allreduce(vals, mesh=mesh, op="mean")
    expect = np.full((3,), np.mean(range(N_DEV)), "float32")
    for o in out:
        assert_almost_equal(o.asnumpy(), expect)
    with pytest.raises(mx.MXNetError):
        parallel.allreduce(vals, mesh=mesh, op="max")


def test_trainer_states_roundtrip(tmp_path, ctxs):
    """update_on_kvstore=True states live in the store (code-review r2)."""
    net = _mlp(seed=17)
    _init_net(net, [ctxs[0]], seed=17)
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": 0.01},
                            kvstore="device", update_on_kvstore=True)
    x = nd.array(np.random.randn(8, 8).astype("float32"))
    y = nd.array(np.random.randn(8, 4).astype("float32"))
    with autograd.record():
        l = ((net(x) - y) ** 2).sum()
    l.backward()
    trainer.step(8)
    fname = str(tmp_path / "states")
    trainer.save_states(fname)
    import pickle
    with open(fname, "rb") as f:
        states = pickle.loads(f.read())
    assert states, "saved optimizer state must not be empty"
    trainer.load_states(fname)
    # invalid combination raises
    with pytest.raises(mx.MXNetError):
        t2 = gluon.Trainer(net.collect_params(), "sgd", kvstore=None,
                           update_on_kvstore=True)
        t2._init_kvstore()


def test_allgather_eager(ctxs):
    from mxnet_tpu import parallel
    vals = [nd.array(np.full((2,), float(i), "float32"), ctx=c)
            for i, c in enumerate(ctxs[:4])]
    out = parallel.allgather(vals)
    expect = np.repeat(np.arange(4, dtype="float32"), 2)
    assert len(out) == 4
    for o in out:
        assert_almost_equal(o.asnumpy(), expect)


def test_allreduce_subset_of_mesh(ctxs):
    """code-review r2: allreduce over fewer devices than the current mesh
    must not crash nor clobber the global mesh."""
    from mxnet_tpu import parallel
    parallel.make_mesh()  # global 8-device mesh
    vals = [nd.array(np.full((2,), float(i + 1), "float32"), ctx=c)
            for i, c in enumerate(ctxs[:4])]
    out = parallel.allreduce(vals)
    for o in out:
        assert_almost_equal(o.asnumpy(), np.full((2,), 10.0, "float32"))
    assert parallel.current_mesh().size == N_DEV  # untouched


# -- TrainStep.run at steady state: exact counts -------------------------------
# What a dispatch of the fused step costs in programs, counted on the CPU: one
# executable for the step, one dispatch a run(), nothing built once warm.  The
# cases are the forms the benchmark's cells dispatch (steps=4, a dp=4 mesh,
# the MLA + MoE decoder whose step has a fourth output, the counters) and the
# forms the repo's other lanes train with.  Counts only: a CPU run says
# nothing about time.

def _token_loss(out, labels):
    logits = out[-1] if isinstance(out, (tuple, list)) else out
    return mx.nd.softmax_cross_entropy(
        logits.reshape((-1, logits.shape[-1])).astype("float32"),
        labels.reshape((-1,))) / labels.size


def _tokens(vocab, *shape):
    r = np.random.RandomState(0)
    return (nd.array(r.randint(0, vocab, shape).astype(np.int32)),
            nd.array(r.randint(0, vocab, shape).astype(np.int32)))


def _dp_mesh(n):
    import jax
    from mxnet_tpu import parallel
    return parallel.make_mesh(shape=(n,), axis_names=("dp",),
                              devices=jax.devices()[:n])


def _bert_step(batch, seq, steps, dp=1, **step_kw):
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon.model_zoo import bert
    net = bert.bert_model("bert_3_128_2", vocab_size=512, max_length=seq,
                          dropout=0.0)
    net.initialize(mx.initializer.Normal(0.02))
    step = parallel.TrainStep(net, _token_loss,
                              mx.optimizer.Adam(learning_rate=1e-4),
                              mesh=_dp_mesh(dp), **step_kw)
    toks, labs = _tokens(512, steps, batch, seq)
    return lambda: step.run(toks, labs)


def _llama_causal_step(**step_kw):
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon.model_zoo.llama import LlamaModel
    net = LlamaModel(vocab_size=512, num_layers=2, units=64, hidden=172,
                     heads=4, kv_heads=2, remat=False)
    net.initialize(mx.initializer.Normal(0.02))
    step = parallel.TrainStep(net, _token_loss,
                              mx.optimizer.Adam(learning_rate=1e-4),
                              mesh=_dp_mesh(1), **step_kw)
    toks, labs = _tokens(512, 1, 2, 256)
    return lambda: step.run(toks, labs)


def _llama_fsdp_step():
    from mxnet_tpu import parallel, sharding
    from mxnet_tpu.gluon.model_zoo.llama import llama_model
    net = llama_model("llama_tiny", vocab_size=64)
    net.initialize(mx.initializer.Normal(0.05))
    step = parallel.TrainStep(
        net, _token_loss, mx.optimizer.Adam(learning_rate=1e-3),
        mesh=parallel.DeviceMesh(shape=(2, 2, 2),
                                 axis_names=("dp", "fsdp", "tp")),
        donate=True, partition_rules=sharding.llama_fsdp_rules(),
        data_spec=("dp",))
    toks, labs = _tokens(64, 16, 16)
    return lambda: step(toks, labs)


def _mla_moe_step():
    from mxnet_tpu import parallel
    from mxnet_tpu.gluon.model_zoo import mla_moe
    net = mla_moe.MLAMoEModel(
        256, 2, 64, 96,
        dict(heads=4, qk_nope=16, qk_rope=8, v_head=16, kv_lora_rank=32,
             rope_base=1e6),
        dict(hidden_size=32, num_experts=16, num_experts_per_token=3,
             experts_held=(4, 8), num_shared_experts=2,
             routed_scaling_factor=2.448), prefix="mlamoe_")
    net.initialize(mx.initializer.Normal(0.02))
    step = parallel.TrainStep(
        net, _token_loss, mx.optimizer.Adam(learning_rate=1e-3),
        mesh=_dp_mesh(1))
    toks, labs = _tokens(256, 2, 2, 16)
    return lambda: step.run(toks, labs)


# case -> (builder, the one-per-op programs of TrainStep._resolve's imperative
#          forward, tokens a dispatch's routed layers report)
_STEADY_CASES = {
    "bert_b4_s32_steps2": (lambda: _bert_step(4, 32, 2), 20, 0),
    "bert_b2_s512_steps2": (lambda: _bert_step(2, 512, 2), 20, 0),
    "llama_causal_steps1": (_llama_causal_step, 38, 0),
    "llama_tiny_dp2_fsdp2_tp2_donated": (_llama_fsdp_step, 37, 0),
    "bert_steps4": (lambda: _bert_step(4, 32, 4), 20, 0),
    "bert_dp4_steps4": (lambda: _bert_step(8, 32, 4, dp=4), 20, 0),
    "mla_moe_counters_steps2": (_mla_moe_step, 59, 2 * 2 * 16),
    "bert_n_micro2": (lambda: _bert_step(4, 32, 2, n_micro=2), 20, 0),
    "llama_causal_remat": (lambda: _llama_causal_step(remat=True), 38, 0),
}


@pytest.mark.parametrize("case", list(_STEADY_CASES))
def test_trainstep_run_steady_state(case, monkeypatch):
    """One warm dispatch, then two that build nothing: every dispatch is
    one call of one executable (``compiles_in_window == 0`` decides
    ``correct`` in every benchmark cell; this holds it before a chip minute
    is spent)."""
    from mxnet_tpu import telemetry
    from mxnet_tpu.analysis.runtime import no_retrace
    from mxnet_tpu.telemetry import REGISTRY, costmodel
    build, resolve_programs, moe_tokens = _STEADY_CASES[case]
    monkeypatch.setenv("MXNET_COSTMODEL_MEMORY", "0")
    mx.random.seed(0)
    dispatch = build()

    def counts():
        return tuple(getattr(REGISTRY.get(name), "value", 0) for name in (
            "mxnet_sharding_step_dispatches_total",
            "mxnet_sharding_retraces_total", "mxnet_moe_tokens_total"))

    telemetry.enable()
    costmodel.disarm()      # arming anew drops the op registry's programs
    costmodel.arm()
    costmodel.LEDGER.clear()
    try:
        d0, t0, m0 = counts()
        assert np.isfinite(dispatch().asnumpy()).all()
        assert counts() == (d0 + 1, t0 + 1, m0 + moe_tokens)
        with no_retrace():
            for _ in range(2):
                assert np.isfinite(dispatch().asnumpy()).all()
        assert counts() == (d0 + 3, t0 + 1, m0 + 3 * moe_tokens)
        sites = costmodel.LEDGER.site_summary()
        step = sites.pop("parallel.TrainStep")
        assert (step["executables"], step["calls"]) == (1, 3)
        # What a first dispatch builds beside the step: _resolve runs the net
        # once imperatively to finish deferred init, one small program an op
        # and shape, results thrown away.  A TrainStep that resolves from
        # shapes alone (as lowered() does) takes this count to 0.
        assert all(s.startswith("op:") for s in sites), sorted(sites)
        resolve_forward_programs = sum(
            s["executables"] for s in sites.values())
        assert resolve_forward_programs == resolve_programs
    finally:
        costmodel.disarm()
        costmodel.LEDGER.clear()
        telemetry.disable()
