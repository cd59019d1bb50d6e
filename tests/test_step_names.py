"""The names the step carries from inside (ISSUE 27): region scopes in the
compiled TrainStep program, forward and backward; the four host spans of a
dispatch on the profiler's clock; the build-stage counters; and that none
of it costs the imperative path a ``named_scope``."""

import os
import re
import subprocess
import sys

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, parallel, regions, telemetry
from mxnet_tpu.gluon.model_zoo import bert

HERE = os.path.dirname(os.path.abspath(__file__))
VOCAB, SEQ, BATCH, STEPS = 64, 16, 4, 2


def tiny_step(n_micro=1):
    """The tiny zoo BERT (bert_3_128_2) under Adam multi_precision in a
    TrainStep on one CPU device, as the benchmark's builder builds it."""
    import jax
    net = bert.bert_model("bert_3_128_2", vocab_size=VOCAB, max_length=SEQ,
                          dropout=0.0, prefix="bert_")
    net.initialize()

    def loss_fn(out, labels):
        _, _, logits = out
        return mx.nd.softmax_cross_entropy(
            logits.reshape((-1, logits.shape[-1])).astype("float32"),
            labels.reshape((-1,))) / labels.size

    opt = mx.optimizer.Adam(learning_rate=1e-3, multi_precision=True)
    mesh = parallel.make_mesh(shape=(1,), axis_names=("dp",),
                              devices=jax.devices()[:1])
    return parallel.TrainStep(net, loss_fn, opt, mesh=mesh, n_micro=n_micro)


def tiny_batches():
    rng = np.random.RandomState(0)
    shape = (STEPS, BATCH, SEQ)
    return (mx.nd.array(rng.randint(0, VOCAB, shape).astype("int32")),
            mx.nd.array(rng.randint(0, VOCAB, shape).astype("int32")))


# -- (a) region scopes in the compiled step, forward and backward ------------

_INSTRUCTION = re.compile(r"^\s+(?:ROOT )?%?[\w.\-]+ = .*?(?:^|[ )])"
                          r"([a-z][a-z0-9\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
# what computes nothing: data movement of the compiler's own and constants
_NO_WORK = {"constant", "parameter", "tuple", "get-tuple-element", "bitcast",
            "broadcast"}
_REGIONS = ("bert", "loss", "optimizer")


def op_names(text):
    """The ``op_name`` of every instruction of a module's text that does
    work and carries one."""
    out = []
    for line in text.splitlines():
        m = _INSTRUCTION.match(line)
        name = _OP_NAME.search(line)
        if m and name and m.group(1) not in _NO_WORK:
            out.append(name.group(1))
    return out


@pytest.fixture(scope="module", params=[1, 2], ids=["whole", "n_micro2"])
def compiled_names(request):
    step = tiny_step(n_micro=request.param)
    text = step.lowered(*tiny_batches()).compile().as_text()
    return op_names(text)


@pytest.mark.parametrize("scope", ["attention", "loss", "optimizer",
                                   "layer1", "attn_qkv", "decoder"])
def test_forward_ops_carry_the_scope(compiled_names, scope):
    hit = [n for n in compiled_names
           if f"/{scope}/" in n and "transpose(" not in n]
    assert hit, f"no forward instruction under {scope!r}"


@pytest.mark.parametrize("scope", ["attention", "loss", "layer1",
                                   "attn_qkv", "decoder"])
def test_backward_ops_carry_the_forwards_scope(compiled_names, scope):
    """The tape calls each op's vjp closure outside the scope the forward
    ran in; it re-enters the scope, so the transposed ops are named."""
    hit = [n for n in compiled_names
           if f"/{scope}/" in n and "transpose(" in n]
    assert hit, f"no backward instruction under {scope!r}"


def test_block_path_is_built_from_registered_names(compiled_names):
    assert any("/bert/encoder/layer2/ffn_1/" in n for n in compiled_names)
    assert any("/bert/encoder/layer0/transpose(jvp(jit(wrapper)))/attention/"
               in n for n in compiled_names)


def test_nine_tenths_of_the_instructions_carry_a_region(compiled_names):
    named = [n for n in compiled_names
             if any(f"/{r}/" in n + "/" for r in _REGIONS)]
    assert len(named) >= 0.9 * len(compiled_names), (
        len(named), len(compiled_names),
        sorted(set(compiled_names) - set(named))[:10])


# -- regions: the package's own path ------------------------------------------

def test_scope_pushes_and_pops_the_path():
    assert regions.current() == ""
    with regions.scope("a"):
        with regions.scope("b"):
            assert regions.current() == "a/b"
        assert regions.current() == "a"
    assert regions.current() == ""


def test_a_child_takes_its_registered_name_the_root_its_own():
    class Net(gluon.Block):
        def __init__(self):
            super().__init__(prefix="net_")
            self.first = gluon.nn.Dense(3, in_units=2)
            self.register_child(gluon.nn.Dense(2, in_units=3), "second")

        def forward(self, x):
            seen.append(regions.current())
            return self._children["second"](self.first(x))

    seen = []
    net = Net()
    assert (net._region, net.first._region,
            net._children["second"]._region) == ("net", "first", "second")
    net.initialize()
    net(mx.nd.ones((1, 2)))
    assert seen == ["net"]


# -- (e) the imperative path pays one attribute store, no named_scope --------

def test_imperative_forward_backward_enters_no_named_scope(monkeypatch):
    import jax
    net = gluon.nn.Dense(3, in_units=2, prefix="d_")
    net.initialize()
    x = mx.nd.ones((4, 2))

    def once():
        with autograd.record():
            loss = (net(x) ** 2).sum()
        node = loss._node[0]
        loss.backward()
        return node

    once()                     # the per-op jits trace here
    entered = []
    real = jax.named_scope
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: entered.append(name) or real(name))
    node = once()
    assert entered == []
    assert node.region == ""                       # recorded outside a block
    with autograd.record():
        out = net(x)
    assert out._node[0].region == "d"              # the tape keeps the path


# -- (d) build-stage counters -------------------------------------------------

def _build_seconds():
    return {stage: getattr(telemetry.REGISTRY.get(
        "mxnet_jit_build_seconds",
        labels={"site": "parallel.TrainStep", "stage": stage}), "value", 0.0)
        for stage in ("trace", "lower", "load")}


def test_build_stages_are_banked_once_with_telemetry_off():
    assert not telemetry.enabled()
    step = tiny_step()
    before = _build_seconds()
    step.run(*tiny_batches()).asnumpy()
    built = _build_seconds()
    for stage in ("trace", "lower", "load"):
        assert built[stage] > before[stage], stage
    # nested jits report their traces inside the outer one's: the union,
    # not the sum, so the stage cannot outgrow the dispatch
    step.run(*tiny_batches()).asnumpy()
    assert _build_seconds() == built


# -- (c) the program's host spans on the profiler's clock ---------------------

def test_trainstep_spans_land_on_the_profilers_host_plane(tmp_path):
    sys.path.insert(0, os.path.dirname(HERE))
    from perfbench import trace_reduce as tr
    env = dict(os.environ, PYTHONPATH=os.pathsep.join(
        [os.path.dirname(HERE), HERE, os.environ.get("PYTHONPATH", "")]))
    done = subprocess.run(
        [sys.executable, os.path.join(HERE, "_trainstep_trace_child.py"),
         str(tmp_path)], env=env, capture_output=True, text=True,
        timeout=240)
    assert done.returncode == 0, done.stderr[-2000:]
    trace = tr.load_xplane(tr.find_xplane(str(tmp_path)),
                           lambda name: name.startswith(tr.HOST_PLANE_PREFIX))
    spans = {}
    for plane in trace["planes"]:
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith("trainstep."):
                    spans.setdefault(name, []).append((start, start + dur))
    assert sorted(spans) == ["trainstep.bookkeeping", "trainstep.enqueue",
                             "trainstep.fetch", "trainstep.h2d",
                             "trainstep.resolve", "trainstep.writeback"]
    assert len(spans.pop("trainstep.resolve")) == 1      # the second step's
    assert {len(v) for v in spans.values()} == {2}       # two dispatches
    first = {k: min(v) for k, v in spans.items()}
    order = ["trainstep.bookkeeping", "trainstep.h2d", "trainstep.enqueue",
             "trainstep.writeback", "trainstep.fetch"]
    for a, b in zip(order, order[1:]):
        assert first[a][1] <= first[b][0], (a, b)


def test_stepclock_names_host_launch_time_enqueue_not_compute():
    telemetry.enable()
    try:
        telemetry.STEP_CLOCK.reset()
        step = tiny_step()
        step.run(*tiny_batches()).asnumpy()
        rec = telemetry.STEP_CLOCK.summary()["phases"]
        assert rec["enqueue"]["median"] > 0 and rec["h2d"]["median"] > 0
        # begin_step to end_step is all noted: nothing is left to land in
        # compute but the clock's own overhead
        assert rec["compute"]["median"] < 0.05 * rec["total"]["median"]
        names = {e["name"] for e in telemetry.get_tracer().events()}
        assert {"trainstep.bookkeeping", "trainstep.h2d",
                "trainstep.enqueue", "trainstep.writeback"} <= names
    finally:
        telemetry.disable()
        telemetry.clear()


# -- TrainStep.optimizer_state ------------------------------------------------

def test_optimizer_state_by_parameter_name():
    import ml_dtypes
    step = tiny_step()
    assert step.optimizer_state() == {}
    step.net.cast(ml_dtypes.bfloat16)
    tokens, labels = tiny_batches()
    step.run(tokens, labels).asnumpy()
    state = step.optimizer_state()
    names = [p.name for p in step.net.collect_params().values()
             if p.grad_req != "null"]
    assert sorted(state) == sorted(names)
    entry = state["bert_decoder_weight"]
    p = step.net.collect_params()["bert_decoder_weight"]
    # bf16 weights under multi_precision: the float32 master and (m, v)
    assert entry["weight"].dtype == np.float32 != p.data().dtype
    assert entry["weight"].shape == p.shape
    m, v = entry["state"]
    assert m.shape == v.shape == p.shape
    assert float(np.abs(m.asnumpy()).max()) > 0
    np.testing.assert_allclose(
        entry["weight"].asnumpy().astype(ml_dtypes.bfloat16).astype("f4"),
        p.data().asnumpy().astype("f4"))


def test_optimizer_state_of_float32_weights_is_the_parameter_itself():
    step = tiny_step()
    step.run(*tiny_batches()).asnumpy()
    entry = step.optimizer_state()["bert_pooler_bias"]
    assert entry["weight"] is step.net.collect_params()[
        "bert_pooler_bias"].data()
    assert len(entry["state"]) == 2


def test_the_programs_name_is_the_one_the_cache_key_holds():
    """JAX's compile-cache key strips the op_name metadata the scopes live
    in but keeps the module's name: a change to the scopes renames the
    program (parallel.TrainStep._make_raw), or a tree that shares a cache
    directory with an older one reads that tree's names."""
    tokens, labels = tiny_batches()
    step = tiny_step()
    assert step.lowered(tokens, labels).as_text().splitlines()[0] \
        .startswith("module @jit_train_steps")
    single = step.lowered(tokens[0], labels[0], scan=False)
    assert single.as_text().splitlines()[0].startswith(
        "module @jit_train_step ")
