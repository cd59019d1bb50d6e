"""GSPMD sharding engine (ISSUE 8): match_partition_rules semantics,
rule packs, TrainStep wiring (rules -> NamedShardings at trace time,
sharded optimizer state, no-retrace), Trainer mesh_reduced allreduce
skip, and the sharded checkpoint round trip."""

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import nd, parallel, sharding
from mxnet_tpu.base import MXNetError
from mxnet_tpu import gluon
from mxnet_tpu.gluon import nn


# ---------------------------------------------------------------------------
# match_partition_rules semantics
# ---------------------------------------------------------------------------

def test_first_match_wins():
    rules = [(r"q_weight$", ("tp", None)),
             (r"weight$", (None, "tp")),
             (r".*", ())]
    specs = sharding.match_partition_rules(
        rules, {"layer0_q_weight": (8, 8), "layer0_o_weight": (8, 8)})
    assert specs["layer0_q_weight"] == ("tp", None)   # rule 1, not rule 2
    assert specs["layer0_o_weight"] == (None, "tp")


def test_tok_weight_shadowing_needs_order():
    """'tok_weight' ends with 'k_weight' — the documented first-match
    guard in llama_rules: the embedding rule must come first."""
    specs = sharding.match_partition_rules(
        sharding.llama_rules(), {"m0_tok_weight": (64, 16),
                                 "m0_layer0_k_weight": (32, 16)})
    assert specs["m0_tok_weight"] == ("tp", None)
    assert specs["m0_layer0_k_weight"] == ("tp", None)


def test_scalars_never_partition():
    rules = [(r".*", ("tp",))]
    specs = sharding.match_partition_rules(
        rules, {"gain": (), "one_elem": (1,), "vec": (8,)})
    assert specs["gain"] == ()
    assert specs["one_elem"] == ()
    assert specs["vec"] == ("tp",)


def test_unmatched_replicates_by_default_and_errors_on_request():
    rules = [(r"q_weight$", ("tp", None))]
    specs = sharding.match_partition_rules(rules, {"stray": (4, 4)})
    assert specs["stray"] == ()
    with pytest.raises(MXNetError, match="stray"):
        sharding.match_partition_rules(rules, {"stray": (4, 4)},
                                       on_unmatched="error")


def test_rule_validation():
    with pytest.raises(MXNetError, match="unknown logical axis"):
        sharding.match_partition_rules([(r".*", ("bogus",))], {"w": (4,)})
    with pytest.raises(MXNetError, match="invalid regex"):
        sharding.match_partition_rules([(r"(", ())], {"w": (4,)})
    # spec rank beyond the param rank is a layout bug, not a fallback
    with pytest.raises(MXNetError, match="rank"):
        sharding.match_partition_rules([(r".*", ("tp", None, None))],
                                       {"w": (4, 4)})


def test_deferred_shape_raises():
    class Leaf:
        shape = None
    with pytest.raises(MXNetError, match="deferred"):
        sharding.match_partition_rules([(r".*", ())], {"w": Leaf()})


# ---------------------------------------------------------------------------
# resolve_spec degradation
# ---------------------------------------------------------------------------

def test_resolve_spec_degrades_absent_axis_and_indivisible_dims():
    mesh = parallel.DeviceMesh(shape=(4, 2), axis_names=("dp", "tp"))
    sh, sharded = sharding.resolve_spec(("tp", None), mesh, shape=(8, 6))
    assert sharded and sh.spec == mesh.spec("tp", None)
    # axis the mesh doesn't carry -> replicated
    sh, sharded = sharding.resolve_spec(("ep", None), mesh, shape=(8, 6))
    assert not sharded and sh.is_fully_replicated
    # indivisible dim (7 % 2) -> that dim unsharded
    sh, sharded = sharding.resolve_spec(("tp", None), mesh, shape=(7, 6))
    assert not sharded and sh.is_fully_replicated
    # multi-axis dim entry ('dp','tp') shards dim0 over 8
    sh, sharded = sharding.resolve_spec((("dp", "tp"),), mesh, shape=(16,))
    assert sharded


def test_mesh_spec_rejects_unknown_axis():
    mesh = parallel.DeviceMesh(shape=(8,), axis_names=("dp",))
    with pytest.raises(MXNetError, match="no axis"):
        mesh.sharded("tp")


# ---------------------------------------------------------------------------
# rule packs over the real zoo param trees
# ---------------------------------------------------------------------------

def _names_with_spec(specs, spec):
    return sorted(n for n, s in specs.items() if s == spec)


def test_llama_pack_covers_every_matrix():
    from mxnet_tpu.gluon.model_zoo.llama import llama_model
    net = llama_model("llama_tiny", vocab_size=64)
    net.initialize(mx.initializer.Normal(0.02))
    specs = sharding.match_partition_rules(
        sharding.llama_rules(), net.collect_params(),
        on_unmatched="error")  # the pack must cover the whole tree
    col = _names_with_spec(specs, ("tp", None))
    row = _names_with_spec(specs, (None, "tp"))
    assert any(n.endswith("tok_weight") for n in col)
    assert any(n.endswith("lm_head_weight") for n in col)
    assert all(n.endswith(("o_weight", "down_weight")) for n in row)
    # norms replicate
    assert all(specs[n] == () for n in specs if n.endswith("norm_weight"))


def test_bert_pack_and_legacy_helper_delegate():
    from mxnet_tpu.gluon.model_zoo import bert
    net = bert.bert_model("bert_3_128_2", vocab_size=100, max_length=16,
                          dropout=0.0)
    net.initialize(mx.initializer.Normal(0.02))
    bert.apply_tp_shardings(net, axis="tp")
    params = net.collect_params()
    assert params["bertmodel0_enc_layer0_attn_qkv_weight"].sharding \
        == ("tp", None)
    assert params["bertmodel0_enc_layer0_ffn2_weight"].sharding \
        == (None, "tp")
    assert params["bertmodel0_word_weight"].sharding == ("tp", None)
    assert params["bertmodel0_embln_gamma"].sharding is None  # replicated


def test_transformer_pack_covers_decoder():
    from mxnet_tpu.gluon.model_zoo.transformer import TransformerModel
    net = TransformerModel(vocab_size=50, num_layers=1, units=16,
                           hidden_size=32, num_heads=2, max_length=8,
                           dropout=0.0)
    net.initialize(mx.initializer.Xavier())
    specs = sharding.match_partition_rules(
        sharding.transformer_rules(), net.collect_params(),
        on_unmatched="error")
    assert any(s == (None, "tp") for s in specs.values())
    assert any(s == ("tp", None) for s in specs.values())


def test_rule_pack_registry():
    assert sharding.rule_pack("llama")[0][1] == ("tp", None)
    with pytest.raises(MXNetError, match="unknown rule pack"):
        sharding.rule_pack("resnet")


# ---------------------------------------------------------------------------
# TrainStep wiring
# ---------------------------------------------------------------------------

class _MLP(gluon.HybridBlock):
    def __init__(self, **kw):
        super().__init__(**kw)
        with self.name_scope():
            self.fc1 = nn.Dense(16, flatten=False, in_units=8,
                                prefix="fc1_")
            self.fc2 = nn.Dense(4, flatten=False, in_units=16,
                                prefix="fc2_")

    def hybrid_forward(self, F, x):
        return self.fc2(F.relu(self.fc1(x)))


_MLP_RULES = [(r"fc1_weight$", ("tp", None)),
              (r"fc2_weight$", (None, "tp")),
              (r"fc1_bias$", ("tp",))]


def _mlp_losses(mesh, rules, steps=3, seed=3):
    mx.random.seed(seed)
    net = _MLP(prefix="mlp_")
    net.initialize(mx.initializer.Xavier())
    step = parallel.TrainStep(net, lambda o, l: gluon.loss.L2Loss()(o, l),
                              mx.optimizer.Adam(learning_rate=1e-2),
                              mesh=mesh, donate=False,
                              partition_rules=rules)
    r = np.random.RandomState(0)
    x = nd.array(r.randn(8, 8).astype(np.float32))
    y = nd.array(r.randn(8, 4).astype(np.float32))
    return net, step, [float(step(x, y).asscalar()) for _ in range(steps)]


def test_trainstep_rules_match_replicated_run():
    mesh = parallel.DeviceMesh(shape=(4, 2), axis_names=("dp", "tp"))
    net_s, step_s, ls = _mlp_losses(mesh, _MLP_RULES)
    net_d, _, ld = _mlp_losses(parallel.DeviceMesh(shape=(8,),
                                                   axis_names=("dp",)), None)
    np.testing.assert_allclose(ls, ld, rtol=2e-5)
    # the rules really landed: param AND its adam state carry tp shardings
    w = net_s.collect_params()["mlp_fc1_weight"]
    assert "tp" in str(w._data._data.sharding.spec)
    i = step_s._trainable.index(w)
    owner_states = [s for s, o in zip(step_s._state_nds, step_s._state_owner)
                    if o == i]
    assert owner_states and all(
        "tp" in str(s._data.sharding.spec) for s in owner_states)


def test_trainstep_rules_no_retrace_and_dispatch_counters():
    from mxnet_tpu.analysis.runtime import no_retrace
    from mxnet_tpu.telemetry import REGISTRY
    import mxnet_tpu.telemetry as tel
    mesh = parallel.DeviceMesh(shape=(4, 2), axis_names=("dp", "tp"))
    net, step, _ = _mlp_losses(mesh, _MLP_RULES, steps=2)
    r = np.random.RandomState(0)
    x = nd.array(r.randn(8, 8).astype(np.float32))
    y = nd.array(r.randn(8, 4).astype(np.float32))
    tel.enable()
    try:
        d0 = REGISTRY.get("mxnet_sharding_step_dispatches_total").value
        t0 = REGISTRY.get("mxnet_sharding_retraces_total").value
        with no_retrace():
            step(x, y)
            step(x, y)
        assert REGISTRY.get(
            "mxnet_sharding_step_dispatches_total").value == d0 + 2
        assert REGISTRY.get("mxnet_sharding_retraces_total").value == t0
    finally:
        tel.disable()


def test_trainstep_rules_authoritative_over_stale_hints():
    """With partition_rules the rule mapping is authoritative: a
    construction-time Parameter.sharding hint must NOT resurrect for an
    unmatched param (the unmatched-replicates bit-identity contract)."""
    mesh = parallel.DeviceMesh(shape=(4, 2), axis_names=("dp", "tp"))
    mx.random.seed(3)
    net = _MLP(prefix="mlp_")
    net.initialize(mx.initializer.Xavier())
    net.collect_params()["mlp_fc2_weight"].sharding = ("tp", None)
    step = parallel.TrainStep(
        net, lambda o, l: gluon.loss.L2Loss()(o, l),
        mx.optimizer.Adam(learning_rate=1e-2), mesh=mesh, donate=False,
        partition_rules=[(r"fc1_weight$", ("tp", None))])
    r = np.random.RandomState(0)
    step(nd.array(r.randn(8, 8).astype(np.float32)),
         nd.array(r.randn(8, 4).astype(np.float32)))
    params = net.collect_params()
    assert params["mlp_fc2_weight"]._data._data.sharding \
        .is_fully_replicated  # unmatched: the stale hint did not win
    assert "tp" in str(params["mlp_fc1_weight"]._data._data.sharding.spec)


def test_trainstep_data_spec_empty_replicates_batch():
    """data_spec=() is an explicit request to replicate the batch — it
    must not fall back to the default dp sharding.  A batch size the dp
    axis doesn't divide (3 over 8 devices) can only run replicated."""
    mesh = parallel.DeviceMesh(shape=(8,), axis_names=("dp",))
    mx.random.seed(3)
    net = _MLP(prefix="mlp_")
    net.initialize(mx.initializer.Xavier())
    step = parallel.TrainStep(net, lambda o, l: gluon.loss.L2Loss()(o, l),
                              mx.optimizer.Adam(learning_rate=1e-2),
                              mesh=mesh, donate=False, data_spec=())
    r = np.random.RandomState(0)
    loss = step(nd.array(r.randn(3, 8).astype(np.float32)),
                nd.array(r.randn(3, 4).astype(np.float32)))
    assert np.isfinite(float(loss.asscalar()))


def test_sharding_coverage_counters_count_each_param_once():
    """resolved + fallback covers EVERY param exactly once per resolve
    (replicated-by-empty-spec params land in fallback), independent of
    step count — the layout-coverage contract."""
    from mxnet_tpu.telemetry import REGISTRY
    import mxnet_tpu.telemetry as tel
    mesh = parallel.DeviceMesh(shape=(4, 2), axis_names=("dp", "tp"))
    tel.enable()
    try:
        r0 = REGISTRY.get("mxnet_sharding_resolved_params_total").value
        f0 = REGISTRY.get("mxnet_sharding_fallback_params_total").value
        net, step, _ = _mlp_losses(mesh, _MLP_RULES, steps=2)
        dr = REGISTRY.get(
            "mxnet_sharding_resolved_params_total").value - r0
        df = REGISTRY.get(
            "mxnet_sharding_fallback_params_total").value - f0
    finally:
        tel.disable()
    assert dr + df == len(step._params)
    assert dr == 3   # fc1_weight, fc2_weight, fc1_bias per _MLP_RULES
    assert df == 1   # fc2_bias: no rule matched -> replicated, counted


def test_trainstep_data_spec_tuple_of_axes():
    """A data_spec entry may shard ONE dim over several mesh axes —
    the same N-axis entries DeviceMesh.spec()/sharded() take."""
    mesh = parallel.DeviceMesh(shape=(2, 2, 2),
                               axis_names=("dp", "tp", "sp"))
    mx.random.seed(3)
    net = _MLP(prefix="mlp_")
    net.initialize(mx.initializer.Xavier())
    step = parallel.TrainStep(net, lambda o, l: gluon.loss.L2Loss()(o, l),
                              mx.optimizer.Adam(learning_rate=1e-2),
                              mesh=mesh, donate=False,
                              data_spec=(("dp", "sp"),))
    r = np.random.RandomState(0)
    loss = step(nd.array(r.randn(8, 8).astype(np.float32)),
                nd.array(r.randn(8, 4).astype(np.float32)))
    assert np.isfinite(float(loss.asscalar()))


def test_trainer_update_on_kvstore_rejects_mesh_reduced():
    """update_on_kvstore=True can't honor mesh_reduced (the store
    reduces inside push — double-count) and must fail loudly."""
    net, ctxs = _two_ctx_net()
    params = net.collect_params()
    params["mlp_fc1_weight"].mesh_reduced = True
    tr = gluon.Trainer(params, "sgd", {"learning_rate": 0.1},
                       kvstore="device", update_on_kvstore=True)
    _set_grads(net, ctxs)
    with pytest.raises(MXNetError, match="mesh_reduced"):
        tr.step(1)


def test_trainstep_data_spec_validates():
    mesh = parallel.DeviceMesh(shape=(8,), axis_names=("dp",))
    with pytest.raises(MXNetError, match="data_spec"):
        parallel.TrainStep(_MLP(), lambda o, l: o, "sgd", mesh=mesh,
                           data_spec=("dp", "sp"))


# ---------------------------------------------------------------------------
# Trainer skips the allreduce for mesh-reduced params
# ---------------------------------------------------------------------------

def _two_ctx_net():
    ctxs = [mx.cpu(0), mx.cpu(1)]
    mx.random.seed(5)
    net = _MLP(prefix="mlp_")
    net.initialize(mx.initializer.Xavier(), ctx=ctxs)
    return net, ctxs


def _set_grads(net, ctxs):
    """Per-ctx grads = (i + 1) * ones, so the reduced value (sum = 3) is
    distinguishable from any single replica's."""
    for p in net.collect_params().values():
        for i, g in enumerate(p.list_grad()):
            g[:] = nd.ones(p.shape, ctx=ctxs[i]) * (i + 1)


def test_trainer_skips_mesh_reduced_params():
    net, ctxs = _two_ctx_net()
    params = net.collect_params()
    marked = params["mlp_fc1_weight"]
    marked.mesh_reduced = True
    tr = gluon.Trainer(params, "sgd", {"learning_rate": 0.1},
                       kvstore="device")
    _set_grads(net, ctxs)
    tr.allreduce_grads()
    # the flagged param kept its per-replica grads (mesh owns them)...
    np.testing.assert_allclose(marked.list_grad()[0].asnumpy(), 1.0)
    np.testing.assert_allclose(marked.list_grad()[1].asnumpy(), 2.0)
    # ...every other param was reduced to the 1+2 sum on both replicas
    other = params["mlp_fc2_weight"]
    for g in other.list_grad():
        np.testing.assert_allclose(g.asnumpy(), 3.0)


def test_trainer_skip_knob_off_restores_reduction(monkeypatch):
    monkeypatch.setenv("MXNET_SHARDING_SKIP_ALLREDUCE", "0")
    net, ctxs = _two_ctx_net()
    params = net.collect_params()
    params["mlp_fc1_weight"].mesh_reduced = True
    tr = gluon.Trainer(params, "sgd", {"learning_rate": 0.1},
                       kvstore="device")
    _set_grads(net, ctxs)
    tr.allreduce_grads()
    for g in params["mlp_fc1_weight"].list_grad():
        np.testing.assert_allclose(g.asnumpy(), 3.0)


def test_mark_mesh_reduced_helper():
    net, _ = _two_ctx_net()
    sharding.mark_mesh_reduced(net)
    assert all(p.mesh_reduced for p in net.collect_params().values())
    sharding.mark_mesh_reduced(net, False)
    assert not any(p.mesh_reduced for p in net.collect_params().values())


# ---------------------------------------------------------------------------
# sharded checkpoint round trip
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("sharded_save", [0, 1])
def test_checkpoint_roundtrips_sharded_params(tmp_path, monkeypatch,
                                              sharded_save):
    pytest.importorskip("orbax.checkpoint")
    monkeypatch.setenv("MXNET_CHECKPOINT_SHARDED", str(sharded_save))
    mesh = parallel.DeviceMesh(shape=(4, 2), axis_names=("dp", "tp"))

    # uninterrupted reference: 4 sharded steps
    net_r, step_r, _ = _mlp_losses(mesh, _MLP_RULES, steps=4)
    ref = {k: p.data().asnumpy().copy()
           for k, p in net_r.collect_params().items()}

    # save after 2 sharded steps (params now carry NamedShardings), then
    # restore into a FRESH net and run the remaining 2
    net_a, step_a, _ = _mlp_losses(mesh, _MLP_RULES, steps=2)
    mgr = mx.checkpoint.CheckpointManager(str(tmp_path / f"s{sharded_save}"))
    assert mgr.save(2, net=net_a)

    net_b, step_b, _ = _mlp_losses(mesh, _MLP_RULES, steps=0)
    got_step, _ = mgr.restore(net=net_b)
    assert got_step == 2
    # adam state must continue too: reuse net_a's live TrainStep states by
    # restoring into net_a itself (param path) — the trainer-states path
    # is covered by test_checkpoint; here the point is the PARAM layout
    mgr.restore(net=net_a)
    r = np.random.RandomState(0)
    x = nd.array(r.randn(8, 8).astype(np.float32))
    y = nd.array(r.randn(8, 4).astype(np.float32))
    for _ in range(2):
        step_a(x, y)
    got = {k: p.data().asnumpy()
           for k, p in net_a.collect_params().items()}
    for k in ref:
        np.testing.assert_allclose(got[k], ref[k], rtol=1e-6, atol=1e-7,
                                   err_msg=k)


# ---------------------------------------------------------------------------
# fsdp rule packs (ISSUE 14): ZeRO-3 resolution edge cases
# ---------------------------------------------------------------------------

def test_fsdp_pack_composes_with_tp_on_same_mesh():
    """llama_fsdp_rules on a dp×fsdp×tp mesh: column-parallel weights
    carry tp on dim0 AND fsdp on dim1; row-parallel the mirror; the
    embedding shards vocab over both."""
    import jax
    mesh = parallel.DeviceMesh(shape=(2, 2, 2),
                               axis_names=("dp", "fsdp", "tp"))
    specs = sharding.match_partition_rules(
        sharding.llama_fsdp_rules(),
        {"m_tok_weight": (64, 16), "m_layer0_q_weight": (16, 16),
         "m_layer0_down_weight": (16, 44), "m_layer0_attn_norm_weight":
         (16,), "m_scale": ()})
    assert specs["m_layer0_q_weight"] == ("tp", "fsdp")
    assert specs["m_layer0_down_weight"] == ("fsdp", "tp")
    assert specs["m_tok_weight"] == (("tp", "fsdp"), None)
    assert specs["m_layer0_attn_norm_weight"] == ()   # norms replicate
    assert specs["m_scale"] == ()                     # scalars never shard
    sh, did = sharding.resolve_spec(specs["m_layer0_q_weight"], mesh,
                                    shape=(16, 16))
    assert did and str(sh.spec) == str(
        jax.sharding.PartitionSpec("tp", "fsdp"))


def test_fsdp_pack_degrades_without_fsdp_axis():
    """The same rule set on a mesh WITHOUT fsdp resolves to the pure tp
    layout (one rule set per model, every mesh) — and on a dp-only mesh
    to full replication."""
    tp_mesh = parallel.DeviceMesh(shape=(4, 2), axis_names=("dp", "tp"))
    sh, did = sharding.resolve_spec(("tp", "fsdp"), tp_mesh,
                                    shape=(16, 16))
    assert did and "tp" in str(sh.spec) and "fsdp" not in str(sh.spec)
    dp_mesh = parallel.DeviceMesh(shape=(8,), axis_names=("dp",))
    sh, did = sharding.resolve_spec(("tp", "fsdp"), dp_mesh,
                                    shape=(16, 16))
    assert not did    # full replication, bit-identity contract


def test_fsdp_indivisible_dim_degrades_to_replicated():
    """A dim not divisible by its fsdp axis (or the tp×fsdp product on
    a combined entry) drops to unsharded instead of erroring."""
    mesh = parallel.DeviceMesh(shape=(2, 2, 2),
                               axis_names=("dp", "fsdp", "tp"))
    # dim1 = 7 not divisible by fsdp=2 -> that dim unsharded, dim0 keeps tp
    sh, did = sharding.resolve_spec(("tp", "fsdp"), mesh, shape=(16, 7))
    assert did
    s = str(sh.spec)
    assert "tp" in s and "fsdp" not in s
    # combined ('tp','fsdp') entry over a dim divisible by 2 but not 4
    sh, did = sharding.resolve_spec((("tp", "fsdp"), None), mesh,
                                    shape=(6, 16))
    assert not did    # 6 % (2*2) != 0 -> whole entry degrades


def test_fsdp_scalar_state_replicates_in_trainstep():
    """Optimizer state that does not match its owner param's shape
    (scalar / odd-shaped state) replicates even under an fsdp pack,
    while same-shaped adam state rides the param's fsdp layout."""
    import numpy as np
    from mxnet_tpu.gluon import nn as gnn, loss as gloss
    mesh = parallel.DeviceMesh(shape=(2, 2, 2),
                               axis_names=("dp", "fsdp", "tp"))
    mx.random.seed(3)
    net = gnn.Dense(16, flatten=False, in_units=16, use_bias=False,
                    prefix="fsdpnet_")
    net.initialize(mx.initializer.Xavier())
    st = parallel.TrainStep(
        net, lambda o, l: gloss.L2Loss()(o, l),
        mx.optimizer.Adam(learning_rate=0.1), mesh=mesh, donate=False,
        partition_rules=[(r"weight$", ("tp", "fsdp"))],
        data_spec=(("dp", "fsdp"),))
    x = np.random.RandomState(0).randn(8, 16).astype("float32")
    y = np.random.RandomState(1).randn(8, 16).astype("float32")
    st(nd.array(x), nd.array(y))
    p_sh, s_sh = st._shardings()
    # every adam m/v state is weight-shaped here: all ride the layout
    assert all("fsdp" in str(sh.spec) for sh in p_sh)
    assert all("fsdp" in str(sh.spec) for sh in s_sh)
    # scalar state (shape != owner param's): the mismatch branch must
    # replicate it — inject one scalar state NDArray next to the real
    # adam slots and re-resolve
    st._state_nds = st._state_nds + [nd.zeros(())]
    st._state_owner = st._state_owner + [0]
    st._p_sh = st._s_sh = None
    _, s_sh2 = st._shardings()
    assert "fsdp" not in str(s_sh2[-1].spec) \
        and str(s_sh2[-1].spec) == "PartitionSpec()"
    assert all("fsdp" in str(sh.spec) for sh in s_sh2[:-1])
