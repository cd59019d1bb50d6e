"""mx.telemetry + mx.profiler facade + mx.monitor hook coverage (ISSUE 1).

Covers: ledger accumulation via record_op, span nesting and the
Chrome-trace JSON schema (parses with json.load; events carry
name/ph/ts/dur), metrics exporter output, profiler state-machine fixes
(scope no-op, pause/stop trace lifecycle, dumps formats, aggregate_stats
off), Monitor install/uninstall symmetry on ops.registry, and the
end-to-end smoke test asserting the dispatch/kvstore/trainer wiring stays
alive.
"""

import json

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, profiler, telemetry
from mxnet_tpu import kvstore as kvs
from mxnet_tpu.base import MXNetError


@pytest.fixture(autouse=True)
def _clean_telemetry():
    """Each test starts and ends with telemetry off and empty."""
    def reset():
        telemetry.disable()
        telemetry.clear()
        telemetry.REGISTRY.reset()
        telemetry.ledger.set_aggregate_stats(True)
        profiler._state["running"] = False
        profiler._state["xla_trace"] = False
        profiler._state["tel_owner"] = False
    reset()
    yield
    reset()


def _events():
    return telemetry.get_tracer().events()


# -- ledger ------------------------------------------------------------------

def test_ledger_accumulation_via_record_op():
    profiler.record_op("opA", 0.002)
    profiler.record_op("opA", 0.004)
    profiler.record_op("opB", 0.001)
    snap = telemetry.ledger.snapshot()
    cnt, tot, mn, mx_ = snap["opA"]
    assert cnt == 2
    assert tot == pytest.approx(0.006)
    assert mn == pytest.approx(0.002)
    assert mx_ == pytest.approx(0.004)
    table = profiler.dumps()
    first_cols = [ln.split()[0] for ln in table.splitlines()[2:]]
    assert first_cols == ["opA", "opB"]  # sorted by total time desc
    # reset=True drains the ledger
    profiler.dumps(reset=True)
    assert telemetry.ledger.snapshot() == {}


def test_set_config_aggregate_stats_off_skips_ledger():
    profiler.set_config(filename="unused.json", aggregate_stats=False)
    profiler.record_op("skipped", 1.0)
    assert telemetry.ledger.snapshot() == {}
    profiler.set_config(filename="unused.json", aggregate_stats=True)
    profiler.record_op("kept", 1.0)
    assert "kept" in telemetry.ledger.snapshot()


def test_dumps_formats():
    profiler.record_op("fmt_op", 0.001)
    table = profiler.dumps()
    assert "Name" in table and "fmt_op" in table
    data = json.loads(profiler.dumps(format="json"))
    assert data["fmt_op"]["calls"] == 1
    assert data["fmt_op"]["total_ms"] == pytest.approx(1.0)
    with pytest.raises(MXNetError):
        profiler.dumps(format="csv")


# -- span tracer -------------------------------------------------------------

def test_span_noop_when_disabled():
    with telemetry.span("invisible", "test") as sp:
        pass
    assert sp is telemetry.NULL_SPAN
    assert _events() == []


def test_span_nesting_and_chrome_schema(tmp_path):
    telemetry.enable()
    with telemetry.span("outer", "test", level=1):
        with telemetry.span("inner", "test"):
            pass
    profiler.set_config(filename=str(tmp_path / "trace.json"))
    profiler.dump()
    with open(tmp_path / "trace.json") as f:
        trace = json.load(f)
    events = trace["traceEvents"]
    spans = {e["name"]: e for e in events if e.get("ph") == "X"}
    assert {"outer", "inner"} <= set(spans)
    for ev in spans.values():
        assert {"name", "ph", "ts", "dur", "pid", "tid", "cat"} <= set(ev)
    outer, inner = spans["outer"], spans["inner"]
    # nesting: inner lies within outer on the timeline
    assert outer["ts"] <= inner["ts"]
    assert inner["ts"] + inner["dur"] <= outer["ts"] + outer["dur"] + 1e-3
    assert outer["args"] == {"level": 1}


def test_tracer_ring_buffer_drops_oldest():
    tr = telemetry.Tracer(capacity=4)
    for i in range(10):
        tr.add_event(f"e{i}", "test", 0, 1)
    evs = tr.events()
    assert len(evs) == 4
    assert evs[0]["name"] == "e6"
    assert tr.dropped == 6
    assert tr.chrome_trace()["otherData"]["droppedEvents"] == 6


def test_instant_events():
    telemetry.enable()
    telemetry.instant("mark", "test", k=2)
    (ev,) = _events()
    assert ev["ph"] == "i" and ev["args"] == {"k": 2}


# -- metrics -----------------------------------------------------------------

def test_counter_and_gauge():
    c = telemetry.counter("t_requests_total", "help text")
    c.inc()
    c.inc(4)
    assert c.value == 5
    with pytest.raises(ValueError):
        c.inc(-1)
    g = telemetry.gauge("t_depth")
    g.set(3)
    g.inc()
    g.dec(2)
    assert g.value == 2
    # get-or-create returns the same object; kind conflicts raise
    assert telemetry.counter("t_requests_total") is c
    with pytest.raises(TypeError):
        telemetry.gauge("t_requests_total")


def test_histogram_buckets():
    h = telemetry.histogram("t_latency_seconds", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.5, 5.0):
        h.observe(v)
    snap = h.snapshot()
    assert snap["buckets"] == {0.01: 1, 0.1: 2, 1.0: 3}
    assert snap["count"] == 4
    assert snap["sum"] == pytest.approx(5.555)
    # get-or-create with the same bounds returns the same histogram;
    # conflicting bounds raise instead of being silently ignored
    assert telemetry.histogram("t_latency_seconds",
                               buckets=(0.01, 0.1, 1.0)) is h
    with pytest.raises(ValueError):
        telemetry.histogram("t_latency_seconds", buckets=(2.0,))


def test_labeled_metrics_series_and_escaping():
    """ISSUE 10 satellite: label support with exposition-format escaping.
    One name may carry several label combinations (each its own series,
    one HELP/TYPE header) and label values escape backslash/quote/newline."""
    a = telemetry.counter("t_phase_total", "per-phase", labels={"phase": "io"})
    b = telemetry.counter("t_phase_total", labels={"phase": "net"})
    assert a is not b
    assert telemetry.counter("t_phase_total", labels={"phase": "io"}) is a
    a.inc(2)
    b.inc(5)
    text = telemetry.to_prometheus()
    assert text.count("# TYPE t_phase_total counter") == 1
    assert 't_phase_total{phase="io"} 2' in text
    assert 't_phase_total{phase="net"} 5' in text
    # stable ordering: the io series renders before net every time
    assert text.index('phase="io"') < text.index('phase="net"')
    assert text == telemetry.to_prometheus()
    # escaping: backslash first, then quote, then newline
    evil = telemetry.counter("t_evil_total",
                             labels={"p": 'a"b\\c\nd'})
    evil.inc()
    assert 't_evil_total{p="a\\"b\\\\c\\nd"} 1' in telemetry.to_prometheus()
    # kind conflicts are caught across label sets of the same name
    with pytest.raises(TypeError):
        telemetry.gauge("t_phase_total", labels={"phase": "other"})
    # json keys carry the label suffix; unlabeled keys stay bare
    data = json.loads(telemetry.to_json())
    assert data['t_phase_total{phase="io"}']["value"] == 2
    assert data['t_phase_total{phase="io"}']["labels"] == {"phase": "io"}


def test_labeled_histogram_renders_le_with_labels():
    h = telemetry.histogram("t_lab_seconds", buckets=(0.5,),
                            labels={"phase": "x"})
    h.observe(0.1)
    text = telemetry.to_prometheus()
    assert 't_lab_seconds_bucket{phase="x",le="0.5"} 1' in text
    assert 't_lab_seconds_bucket{phase="x",le="+Inf"} 1' in text
    assert 't_lab_seconds_sum{phase="x"} 0.1' in text
    assert 't_lab_seconds_count{phase="x"} 1' in text


def test_histogram_inf_bound_normalized():
    """An explicit +Inf bound must not render a duplicate +Inf row: the
    implicit tail bucket is THE +Inf bucket, emitted exactly once."""
    h = telemetry.histogram("t_inf_seconds",
                            buckets=(0.1, float("inf"), 0.5, 0.5))
    assert h.buckets == (0.1, 0.5)   # dedup + inf dropped
    h.observe(9.0)
    text = telemetry.to_prometheus()
    assert text.count('t_inf_seconds_bucket{le="+Inf"}') == 1
    assert 't_inf_seconds_bucket{le="+Inf"} 1' in text
    with pytest.raises(ValueError):
        telemetry.histogram("t_only_inf", buckets=(float("inf"),))


def test_histogram_absorb_merges_raw_counts():
    h1 = telemetry.Histogram("m", buckets=(0.1, 1.0))
    h2 = telemetry.Histogram("m", buckets=(0.1, 1.0))
    h1.observe(0.05)
    h2.observe(0.5)
    h2.observe(5.0)
    h1._absorb(*h2._raw())
    snap = h1.snapshot()
    assert snap["count"] == 3
    assert snap["buckets"] == {0.1: 1, 1.0: 2}
    assert snap["sum"] == pytest.approx(5.55)
    # mismatched bounds: count/sum stay truthful via the +Inf tail
    h3 = telemetry.Histogram("m", buckets=(7.0,))
    h3.observe(1.0)
    h1._absorb(*h3._raw())
    snap = h1.snapshot()
    assert snap["count"] == 4 and snap["buckets"] == {0.1: 1, 1.0: 2}


def test_prometheus_and_json_export():
    telemetry.counter("t_ops_total", "ops").inc(7)
    telemetry.histogram("t_seconds", "lat", buckets=(0.5,)).observe(0.1)
    text = telemetry.to_prometheus()
    assert "# TYPE t_ops_total counter" in text
    assert "t_ops_total 7" in text
    assert "# TYPE t_seconds histogram" in text
    assert 't_seconds_bucket{le="0.5"} 1' in text
    assert 't_seconds_bucket{le="+Inf"} 1' in text
    assert "t_seconds_count 1" in text
    data = json.loads(telemetry.to_json())
    assert data["t_ops_total"]["value"] == 7
    assert data["t_seconds"]["type"] == "histogram"


# -- profiler state machine (satellite fixes) --------------------------------

def test_scope_cheap_noop_when_stopped():
    with profiler.scope("idle"):
        pass
    assert telemetry.ledger.snapshot() == {}
    assert _events() == []


def test_scope_is_one_span_on_both_timelines(monkeypatch):
    """profiler.scope has no annotation path of its own: the telemetry span
    it opens is the ring-buffer event and the profiler's TraceAnnotation,
    under one name."""
    import jax
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: None, raising=False)
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: None, raising=False)
    annotated = []

    class Annotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            annotated.append(self.name)

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)
    profiler.start()
    with profiler.scope("annotated"):
        pass
    profiler.stop()
    assert annotated == ["scope:annotated"]
    assert "scope:annotated" in telemetry.ledger.snapshot()
    assert [e["name"] for e in _events()] == ["scope:annotated"]


def test_pause_then_stop_closes_xla_trace(monkeypatch):
    import jax
    calls = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: calls.append("start"), raising=False)
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: calls.append("stop"), raising=False)
    profiler.start()
    assert telemetry.enabled()
    profiler.pause()
    assert not profiler.is_running()
    assert not telemetry.enabled()          # host recording suspended
    assert profiler._state["xla_trace"]     # device trace still open
    profiler.resume()
    assert profiler.is_running() and telemetry.enabled()
    profiler.pause()
    profiler.stop()                          # must close the device trace
    assert calls == ["start", "stop"]
    assert not profiler._state["xla_trace"]
    assert not telemetry.enabled()


def test_start_begins_fresh_trace_window(monkeypatch):
    """Back-to-back profile sessions must not leak spans across dump()s."""
    import jax
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: None, raising=False)
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: None, raising=False)
    profiler.start()
    with telemetry.span("workload_a", "test"):
        pass
    profiler.stop()
    profiler.start()
    assert _events() == []  # session A's spans dropped
    with telemetry.span("workload_b", "test"):
        pass
    profiler.stop()
    names = {e["name"] for e in _events()}
    assert "workload_b" in names and "workload_a" not in names


def test_profiler_does_not_steal_user_enabled_telemetry(monkeypatch):
    import jax
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: None, raising=False)
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: None, raising=False)
    telemetry.enable()
    profiler.start()
    profiler.stop()
    assert telemetry.enabled()  # user turned it on; stop() leaves it on


# -- monitor hook symmetry ---------------------------------------------------

def test_monitor_install_uninstall_symmetry():
    from mxnet_tpu.monitor import Monitor
    from mxnet_tpu.ops import registry as reg
    n0 = len(reg._monitor_hooks)
    mon = Monitor(interval=1)
    mon.install()
    mon.install()  # idempotent
    assert len(reg._monitor_hooks) == n0 + 1
    mon.uninstall()
    assert len(reg._monitor_hooks) == n0
    mon.uninstall()  # idempotent
    assert len(reg._monitor_hooks) == n0


def test_monitor_hook_overhead_metric():
    from mxnet_tpu.monitor import Monitor
    telemetry.enable()
    mon = Monitor(interval=1)
    mon.install()
    try:
        mon.tic()
        _ = mx.nd.ones((2, 2)) + 1
        assert mon.toc()  # stats collected through the dispatch hook
        assert telemetry.histogram("mxnet_monitor_hook_seconds").count >= 1
    finally:
        mon.uninstall()


# -- end-to-end wiring (CI smoke: keeps instrumentation from rotting) --------

def test_train_step_telemetry_smoke(tmp_path):
    assert mx.telemetry is telemetry  # lazy top-level name resolves
    telemetry.enable()
    net = gluon.nn.Dense(4, in_units=3)
    net.initialize()
    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": 0.1},
                            kvstore=kvs.create("local"))
    x = mx.nd.array(np.ones((2, 3), np.float32))
    with autograd.record():
        loss = (net(x) ** 2).sum()
    loss.backward()
    trainer.step(2)

    out = tmp_path / "trace.json"
    profiler.set_config(filename=str(out))
    profiler.dump()
    with open(out) as f:
        trace = json.load(f)
    cats = {e.get("cat") for e in trace["traceEvents"] if e.get("ph") == "X"}
    assert {"dispatch", "kvstore", "trainer"} <= cats
    names = {e["name"] for e in trace["traceEvents"]}
    # dense grads ride the fused bucket path (ISSUE 2); per-key
    # kvstore.push/pull spans only appear on the fallback paths.  With
    # the fused optimizer on (ISSUE 5, the default) the reduced buckets
    # stay FLAT (pushpull_flat); either fused span proves it
    assert {"trainer.step", "trainer.allreduce"} <= names
    assert {"kvstore.fused_pushpull", "kvstore.fused_pushpull_flat"} & names
    assert trace["otherData"]["opAggregates"]  # per-op ledger rides along

    text = telemetry.to_prometheus()
    assert "mxnet_op_dispatch_total" in text
    assert "mxnet_op_dispatch_seconds_bucket" in text
    assert telemetry.counter("mxnet_op_dispatch_total").value > 0
    assert telemetry.counter("mxnet_kvstore_fused_bytes_total").value > 0
    assert telemetry.counter("mxnet_trainer_steps_total").value == 1


def test_dataloader_telemetry():
    telemetry.enable()
    ds = gluon.data.ArrayDataset(mx.nd.array(np.arange(12).reshape(6, 2)))
    n = sum(1 for _ in gluon.data.DataLoader(ds, batch_size=3))
    assert n == 2
    assert telemetry.counter("mxnet_dataloader_batches_total").value == 2
    assert telemetry.histogram("mxnet_dataloader_batch_seconds").count == 2
    assert "data" in {e.get("cat") for e in _events()}


def test_checkpoint_telemetry(tmp_path):
    pytest.importorskip("orbax.checkpoint")
    from mxnet_tpu import checkpoint
    telemetry.enable()
    mgr = checkpoint.CheckpointManager(str(tmp_path / "ckpt"), max_to_keep=1)
    mgr.save(0, extra={"w": mx.nd.ones((2, 2))})
    step, extra = mgr.restore()
    assert step == 0 and "w" in extra
    cats = {e.get("cat") for e in _events()}
    assert "checkpoint" in cats
    assert telemetry.histogram("mxnet_checkpoint_save_seconds").count >= 1
    assert telemetry.histogram("mxnet_checkpoint_restore_seconds").count >= 1


def test_disabled_dispatch_records_nothing():
    _ = mx.nd.ones((2, 2)) * 2
    assert telemetry.counter("mxnet_op_dispatch_total").value == 0
    assert _events() == []


# -- profiler facade paths the ISSUE-12 rewrites left thin -------------------

def test_nested_scope_ledger_and_spans(monkeypatch, tmp_path):
    """scope() nests: both levels land in the span buffer AND the per-op
    aggregate ledger, and the inner span lies within the outer one."""
    import jax
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: None, raising=False)
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: None, raising=False)
    profiler.set_config(filename=str(tmp_path / "p.json"))
    profiler.start()
    with profiler.scope("outer"):
        _ = (mx.nd.ones((4, 4)) * 2).asnumpy()
        with profiler.scope("inner"):
            _ = (mx.nd.ones((4, 4)) + 1).asnumpy()
    profiler.stop()
    snap = telemetry.ledger.snapshot()
    assert snap["scope:outer"][0] == 1
    assert snap["scope:inner"][0] == 1
    # a nested scope's time is contained in its parent's
    assert snap["scope:inner"][1] <= snap["scope:outer"][1]
    spans = {e["name"]: e for e in _events() if e.get("ph") == "X"}
    assert {"scope:outer", "scope:inner"} <= set(spans)
    o, i = spans["scope:outer"], spans["scope:inner"]
    assert o["ts"] <= i["ts"]
    assert i["ts"] + i["dur"] <= o["ts"] + o["dur"] + 1  # 1us rounding


def test_pause_resume_around_dump(monkeypatch, tmp_path):
    """pause() stops host recording but dump() still renders what was
    captured; resume() continues into the same session; stop() after a
    pause still closes the device trace exactly once."""
    import jax
    stops = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda *a, **k: None, raising=False)
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: stops.append(1), raising=False)
    profiler.set_config(filename=str(tmp_path / "p.json"))
    profiler.start()
    with profiler.scope("before_pause"):
        pass
    profiler.pause()
    assert not profiler.is_running()
    assert profiler._state["xla_trace"]          # device trace stays open
    with profiler.scope("while_paused"):         # cheap no-op: not recorded
        pass
    profiler.dump()                              # dump mid-pause works
    with open(tmp_path / "p.json") as f:
        trace = json.load(f)
    names = {e["name"] for e in trace["traceEvents"]}
    assert "scope:before_pause" in names
    assert "scope:while_paused" not in names
    assert "scope:before_pause" in trace["otherData"]["opAggregates"]
    profiler.resume()
    with profiler.scope("after_resume"):
        pass
    profiler.stop()
    assert stops == [1]                          # closed exactly once
    snap = telemetry.ledger.snapshot()
    assert "scope:after_resume" in snap
    assert "scope:while_paused" not in snap


def test_aggregate_stats_off_with_cost_ledger_armed():
    """aggregate_stats=False turns the per-op aggregate OFF without
    touching the ISSUE-12 cost ledger: an armed dispatch still records
    its executable while the profiler table stays empty."""
    from mxnet_tpu.telemetry import costmodel
    profiler.set_config(filename="unused.json", aggregate_stats=False)
    telemetry.enable()
    costmodel.LEDGER.clear()
    costmodel.arm()
    try:
        _ = (mx.nd.ones((8, 8)) @ mx.nd.ones((8, 8))).asnumpy()
        assert telemetry.ledger.snapshot() == {}         # aggregate off
        sites = {e["site"] for e in costmodel.LEDGER.entries()}
        assert any(s.startswith("op:") for s in sites)   # ledger on
        assert telemetry.counter("mxnet_op_dispatch_total").value >= 1
    finally:
        costmodel.disarm()
        costmodel.LEDGER.clear()
