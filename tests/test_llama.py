"""Llama stretch-config tests (BASELINE config 5): architecture
correctness + TP-sharded train step over a dp×tp mesh."""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, parallel
from mxnet_tpu.gluon.model_zoo import llama


def _tiny(vocab=101):
    net = llama.llama_model("llama_tiny", vocab_size=vocab)
    net.initialize(mx.initializer.Normal(0.02))
    return net


def test_forward_shape_and_causality(seeded):
    net = _tiny()
    toks = mx.nd.array(np.random.RandomState(0).randint(0, 101, (2, 16)))
    out = net(toks)
    assert out.shape == (2, 16, 101)
    mutated = toks.asnumpy().copy()
    mutated[:, 10:] = 7
    out2 = net(mx.nd.array(mutated))
    # causal: earlier logits are independent of later tokens
    np.testing.assert_allclose(out.asnumpy()[:, :10],
                               out2.asnumpy()[:, :10], atol=1e-5)
    assert not np.allclose(out.asnumpy()[:, 10:], out2.asnumpy()[:, 10:])


def test_gqa_head_counts():
    blk = llama.LlamaBlock(64, 172, heads=4, kv_heads=2)
    blk.initialize()
    x = mx.nd.ones((2, 8, 64))
    assert blk(x).shape == (2, 8, 64)
    p = blk.collect_params()
    kw = next(v for k, v in p.items() if k.endswith("k_weight"))
    qw = next(v for k, v in p.items() if k.endswith("q_weight"))
    assert kw.shape[0] == qw.shape[0] // 2  # kv projection half-sized


def test_rmsnorm_matches_reference(seeded):
    norm = llama.RMSNorm(8)
    norm.initialize()
    x = np.random.RandomState(1).randn(3, 8).astype(np.float32)
    out = norm(mx.nd.array(x)).asnumpy()
    ref = x / np.sqrt((x ** 2).mean(-1, keepdims=True) + 1e-5)
    np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-6)


def test_training_reduces_loss(seeded):
    net = _tiny()
    toks = mx.nd.array(np.random.RandomState(0).randint(0, 101, (4, 12)))
    tr = gluon.Trainer(net.collect_params(), "adam",
                       {"learning_rate": 3e-3})
    lossf = gluon.loss.SoftmaxCrossEntropyLoss()
    losses = []
    for _ in range(6):
        with autograd.record():
            logits = net(toks)
            loss = lossf(logits.reshape((-1, 101)),
                         mx.nd.array(toks.asnumpy().reshape(-1)))
        loss.backward()
        tr.step(4)
        losses.append(float(loss.mean().asnumpy()))
    assert losses[-1] < losses[0]


def test_tp_sharding_annotations():
    net = _tiny()
    llama.apply_tp_shardings(net, axis="tp")
    p = net.collect_params()
    col = next(v for k, v in p.items() if k.endswith("gate_weight"))
    row = next(v for k, v in p.items() if k.endswith("down_weight"))
    emb = next(v for k, v in p.items() if k.endswith("tok_weight"))
    assert col.sharding == ("tp", None)
    assert row.sharding == (None, "tp")
    assert emb.sharding == ("tp", None)


def test_tp_dp_mesh_train_step(seeded):
    """The stretch acceptance: full train step jitted over a dp×tp mesh
    with megatron shardings — the llama analog of dryrun_multichip."""
    import jax
    if len(jax.devices()) < 4:
        pytest.skip("needs 4 devices")
    mesh = parallel.DeviceMesh(shape=(2, 2), axis_names=("dp", "tp"),
                               devices=jax.devices()[:4])
    net = llama.llama_model("llama_tiny", vocab_size=64)
    net.initialize(mx.initializer.Normal(0.02))
    llama.apply_tp_shardings(net, axis="tp")

    def loss_fn(logits, labels):
        return mx.nd.softmax_cross_entropy(
            logits.reshape((-1, logits.shape[-1])).astype("float32"),
            labels.reshape((-1,))) / labels.size

    opt = mx.optimizer.Adam(learning_rate=1e-3)
    step = parallel.TrainStep(net, loss_fn, opt, mesh=mesh)
    r = np.random.RandomState(0)
    toks = mx.nd.array(r.randint(0, 64, (8, 16)).astype(np.int32))
    losses = [float(step(toks, toks).asnumpy()) for _ in range(3)]
    assert losses[-1] < losses[0]
    assert all(np.isfinite(l) for l in losses)


@pytest.mark.slow  # >10s on the tier-1 budget clock (r7 audit); runs in the CI slow lane
@pytest.mark.parametrize("impl", ["ring", "ulysses"])
def test_sp_attention_train_step_parity(impl, seeded):
    """Sequence-parallel llama (contrib.sp_att_qkv over a dp×sp mesh)
    reproduces the dense-attention train-step loss exactly — the dryrun
    'sp' lane as a pytest."""
    from mxnet_tpu import nd
    vocab, seq = 64, 16
    mesh = parallel.DeviceMesh(shape=(2, 4), axis_names=("dp", "sp"))
    r = np.random.RandomState(7)
    toks = r.randint(0, vocab, (4, seq)).astype("int32")
    labs = np.roll(toks, -1, axis=1).astype("int32")

    def loss_fn(o, l):
        return mx.nd.softmax_cross_entropy(
            o.reshape((-1, o.shape[-1])), l.reshape((-1,))) / l.size

    losses = {}
    prev = parallel.current_mesh()
    try:
        for cur_impl, m in (("fused", None), (impl, mesh)):
            parallel.set_mesh(m)
            mx.random.seed(11)
            net = llama.llama_model("llama_tiny", vocab_size=vocab,
                                    attn_impl=cur_impl)
            net.initialize(mx.initializer.Normal(0.05))
            step = parallel.TrainStep(
                net, loss_fn, mx.optimizer.Adam(learning_rate=1e-3),
                mesh=mesh, donate=False)
            losses[cur_impl] = float(step(
                nd.array(toks, dtype="int32"),
                nd.array(labs, dtype="int32")).asscalar())
    finally:
        parallel.set_mesh(prev)
    assert np.isfinite(losses[impl])
    np.testing.assert_allclose(losses[impl], losses["fused"], rtol=2e-4)


def test_sp_att_qkv_no_mesh_fallback(seeded):
    """Without an active mesh the sp op degrades to local attention and
    matches masked_att_qkv (full valid_length, causal)."""
    r = np.random.RandomState(3)
    B, H, L, D = 2, 4, 16, 8
    q = mx.nd.array(r.randn(B, H, L, D).astype("float32"))
    k = mx.nd.array(r.randn(B, H // 2, L, D).astype("float32"))
    v = mx.nd.array(r.randn(B, H // 2, L, D).astype("float32"))
    out_sp = mx.nd.contrib.sp_att_qkv(q, k, v, impl="ring", axis="sp",
                                      num_kv_groups=2, causal=True)
    vl = mx.nd.array(np.full((B,), L, np.float32))
    out_ref = mx.nd.contrib.masked_att_qkv(q, k, v, vl, num_kv_groups=2,
                                           causal=True)
    np.testing.assert_allclose(out_sp.asnumpy(), out_ref.asnumpy(),
                               rtol=1e-5, atol=1e-6)


def test_llama_remat_parity():
    """MXNET_BACKWARD_DO_MIRROR analog: remat per decoder block gives the
    SAME forward and gradients as the stored-activation path (gluon.utils
    .remat_call underneath — jax.checkpoint recompute in backward)."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import autograd
    from mxnet_tpu.gluon.model_zoo.llama import LlamaModel

    r = np.random.RandomState(0)
    toks = mx.nd.array(r.randint(0, 64, (2, 16)).astype(np.int32))

    losses, grads = [], []
    for remat in (False, True):
        mx.random.seed(0)
        m = LlamaModel(vocab_size=64, num_layers=2, units=32, hidden=96,
                       heads=4, kv_heads=2, remat=remat,
                       prefix=f"remat{int(remat)}_")
        m.initialize(mx.initializer.Normal(0.05))
        with autograd.record():
            out = m(toks)
            loss = (out.astype("float32") ** 2).mean()
        loss.backward()
        losses.append(float(loss.asnumpy()))
        g = {k.split("_", 1)[1]: p.data().grad.asnumpy().copy()
             for k, p in m.collect_params().items()
             if p.data().grad is not None}
        grads.append(g)
    assert np.allclose(losses[0], losses[1], rtol=1e-5)
    assert set(grads[0]) == set(grads[1]) and len(grads[0]) > 4
    for k in grads[0]:
        np.testing.assert_allclose(grads[0][k], grads[1][k],
                                   rtol=1e-4, atol=1e-6, err_msg=k)


def test_llama_remat_trainstep():
    """The remat path must trace through parallel.TrainStep (the bench
    llama lane's exact mechanism) and match the no-remat loss."""
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import nd, parallel
    from mxnet_tpu.gluon.model_zoo.llama import LlamaModel

    r = np.random.RandomState(0)
    toks = r.randint(0, 64, (1, 8, 16)).astype(np.int32)
    labs = r.randint(0, 64, (1, 8, 16)).astype(np.int32)

    losses = []
    for remat in (False, True):
        mx.random.seed(0)
        model = LlamaModel(vocab_size=64, num_layers=2, units=32, hidden=96,
                           heads=4, kv_heads=2, remat=remat,
                           prefix=f"ts_remat{int(remat)}_")
        model.initialize(mx.initializer.Normal(0.05))

        def loss_fn(out, labels):
            return mx.nd.softmax_cross_entropy(
                out.reshape((-1, out.shape[-1])).astype("float32"),
                labels.reshape((-1,))) / labels.size

        step = parallel.TrainStep(model, loss_fn,
                                  mx.optimizer.Adam(learning_rate=1e-3),
                                  mesh=parallel.make_mesh())
        out = step.run(nd.array(toks), nd.array(labs))
        losses.append(float(np.asarray(out.asnumpy())[-1]))
    assert np.allclose(losses[0], losses[1], rtol=1e-5), losses


@pytest.mark.parametrize("keep", ["inputs", "products"])
def test_remat_call_keeps_its_inputs_alone_unless_a_policy_keeps_more(keep):
    """``remat_call``'s contract, which ``use_remat`` and
    ``TrainStep(remat=True)`` rely on: with ``keep=None`` the vjp holds the
    block's input and parameters and the rotary tables the forward makes
    from constants, no activation; a policy adds what it keeps.  Each call
    under recording grows the two counters under its ``keep`` label."""
    import jax
    from mxnet_tpu import telemetry
    from mxnet_tpu.gluon.model_zoo.llama import LlamaModel
    from mxnet_tpu.gluon.utils import remat_call

    m = LlamaModel(vocab_size=64, num_layers=1, units=32, hidden=96,
                   heads=4, kv_heads=2, prefix=f"keep_{keep}_")
    m.initialize(mx.initializer.Normal(0.05))
    m(mx.nd.array(np.zeros((2, 16), np.int32)))
    blk = m.blocks[0]
    x = mx.nd.array(np.random.RandomState(1).randn(2, 16, 32)
                    .astype("float32"))
    policy = None if keep == "inputs" else \
        jax.checkpoint_policies.dots_with_no_batch_dims_saveable
    telemetry.REGISTRY.reset()
    with autograd.record():
        y = remat_call(blk, x, keep=policy)
    weights = {id(p.data()._data) for p in blk.collect_params().values()}
    leaves = jax.tree_util.tree_leaves(y._node[0].vjp_fn)
    held = [a for a in leaves if id(a) not in weights]
    assert len(leaves) - len(held) == len(weights)
    assert held[0] is x._data
    tables = [a.shape for a in held[1:] if a.ndim == 1]
    assert sorted(tables) == [(4,), (4,), (16,), (16,)]
    activations = [a.shape for a in held[1:] if a.ndim > 1]
    if keep == "inputs":
        assert activations == []
    else:       # q, k, v, the attention's output, gate and up
        assert sorted(activations) == sorted(
            [(2, 16, 32), (2, 16, 16), (2, 16, 16), (2, 16, 32),
             (2, 16, 96), (2, 16, 96)])
    got = {(mt.name, dict(mt.labels)["keep"]): mt.value
           for mt in telemetry.REGISTRY.collect()
           if mt.name.startswith("mxnet_remat_") and mt.value}
    assert got == {
        ("mxnet_remat_applications_total", keep): 1,
        ("mxnet_remat_kept_bytes_total", keep):
            sum(a.size * a.dtype.itemsize for a in held)}
    # outside a recording nothing is held, nothing counted
    remat_call(blk, x, keep=policy)
    assert telemetry.REGISTRY.get("mxnet_remat_applications_total",
                                  labels={"keep": keep}).value == 1
