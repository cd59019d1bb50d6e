"""Distributed observability plane (ISSUE 10): cross-process aggregation,
step-time attribution, and the crash flight recorder.

Covers: snapshot export/merge round trips (in-process, and across real
subprocesses through the MXNET_TELEMETRY_DIR collection protocol), merged
Chrome-trace metadata (pid=rank, process/thread names, shared timeline),
merged Prometheus summation, StepClock phase accounting and the
input-/comms-/compute-bound verdicts on REAL runs (slow DataLoader →
input-bound; chaos-delayed allreduce → comms-bound), telemetry.report(),
the tools/telemetry_report.py CLI, and flight-recorder dumps on every
trigger (unhandled exception, chaos 'exit', deadline-exceeded, SIGUSR2).
"""

import json
import os
import signal
import subprocess
import sys
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import autograd, gluon, telemetry
from mxnet_tpu.telemetry import aggregate, flightrec, stepclock

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_telemetry():
    def reset():
        telemetry.disable()
        telemetry.clear()
        telemetry.REGISTRY.reset()
        aggregate.set_rank(None)
        telemetry.get_tracer().set_process_label("mxnet_tpu")
        from mxnet_tpu.resilience import chaos
        chaos.clear()
    reset()
    yield
    reset()


def _subprocess(code, env=None, timeout=120):
    full_env = {**os.environ, "JAX_PLATFORMS": "cpu", **(env or {})}
    return subprocess.run([sys.executable, "-c", code], cwd=REPO,
                          capture_output=True, text=True, timeout=timeout,
                          env=full_env)


# ---------------------------------------------------------------------------
# aggregation: snapshot export + merge
# ---------------------------------------------------------------------------

def test_snapshot_export_atomic_roundtrip(tmp_path):
    telemetry.enable()
    with telemetry.span("work", "test", k=1):
        pass
    telemetry.counter("t_obs_total").inc(7)
    path = aggregate.export_snapshot(directory=str(tmp_path))
    assert os.path.basename(path).startswith("telemetry-rank00000-")
    assert not [f for f in os.listdir(tmp_path) if ".tmp." in f]
    with open(path) as f:
        snap = json.load(f)
    assert snap["version"] == aggregate.SNAPSHOT_VERSION
    assert snap["rank"] == 0 and snap["pid"] == os.getpid()
    assert any(e["name"] == "work" for e in snap["events"])
    assert any(m["name"] == "t_obs_total" and m["value"] == 7
               for m in snap["metrics"])
    assert snap["wall_anchor_us"] > 0
    assert snap["stepclock"]["verdict"] == "idle"
    # re-export from the same process replaces the same file
    assert aggregate.export_snapshot(directory=str(tmp_path)) == path
    assert len(aggregate.load_snapshots(str(tmp_path))) == 1


def test_merged_chrome_trace_and_prometheus(tmp_path):
    telemetry.enable()
    with telemetry.span("step", "test"):
        pass
    telemetry.counter("t_merge_total").inc(3)
    telemetry.histogram("t_merge_seconds", buckets=(0.5, 1.0)).observe(0.1)
    aggregate.export_snapshot(directory=str(tmp_path))          # rank 0
    aggregate.set_rank(1)
    telemetry.counter("t_merge_total").inc(2)                   # now 5
    aggregate.export_snapshot(directory=str(tmp_path))          # rank 1
    snaps = aggregate.load_snapshots(str(tmp_path))
    assert [s["rank"] for s in snaps] == [0, 1]

    trace = aggregate.merged_chrome_trace(snaps)
    evs = trace["traceEvents"]
    names = [(e["name"], e.get("pid")) for e in evs if e.get("ph") == "M"]
    assert ("process_name", 0) in names and ("process_name", 1) in names
    assert ("process_sort_index", 0) in names
    assert any(n == "thread_name" for n, _ in names)
    spans = [e for e in evs if e.get("ph") == "X"]
    assert {e["pid"] for e in spans} == {0, 1}  # pid rewritten to rank
    labels = {e["args"]["name"] for e in evs
              if e["name"] == "process_name"}
    assert "mxnet_tpu rank 1" in labels

    prom = aggregate.merged_prometheus(snaps)
    # counters sum across ranks: 3 (rank0) + 5 (rank1 exported later)
    assert "t_merge_total 8" in prom
    # histogram buckets sum too (one observation per snapshot)
    assert 't_merge_seconds_bucket{le="0.5"} 2' in prom
    assert 't_merge_seconds_bucket{le="+Inf"} 2' in prom


def test_merge_skips_corrupt_shards(tmp_path):
    telemetry.enable()
    aggregate.export_snapshot(directory=str(tmp_path))
    with open(tmp_path / "telemetry-rank00009-pid1.json", "w") as f:
        f.write("{ truncated")
    snaps = aggregate.load_snapshots(str(tmp_path))
    assert [s["rank"] for s in snaps] == [0]


def test_two_subprocess_collection_roundtrip(tmp_path):
    """The real protocol end to end: two separate processes, telemetry on,
    MXNET_TELEMETRY_DIR set, export at EXIT (atexit, no explicit call);
    this process plays rank 0's merge role."""
    code = """
import mxnet_tpu as mx
from mxnet_tpu import telemetry
with telemetry.span('subwork', 'test'):
    pass
telemetry.counter('t_sub_total').inc(4)
"""
    procs = [subprocess.Popen(
        [sys.executable, "-c", code], cwd=REPO,
        stdout=subprocess.PIPE, stderr=subprocess.PIPE,
        env={**os.environ, "JAX_PLATFORMS": "cpu", "MXNET_TELEMETRY": "1",
             "MXNET_TELEMETRY_DIR": str(tmp_path),
             "MXNET_DIST_RANK": str(r)}) for r in (0, 1)]
    for p in procs:
        _, err = p.communicate(timeout=120)
        assert p.returncode == 0, err.decode()[-500:]
    snaps = aggregate.load_snapshots(str(tmp_path))
    assert [s["rank"] for s in snaps] == [0, 1]
    trace = aggregate.merged_chrome_trace(snaps)
    sub = [e for e in trace["traceEvents"] if e["name"] == "subwork"]
    assert {e["pid"] for e in sub} == {0, 1}
    assert "t_sub_total 8" in aggregate.merged_prometheus(snaps)


def test_counter_delta_shipping_inprocess():
    """The decode-pool ack-channel protocol, worker side + parent side."""
    c = telemetry.counter("t_ship_total")
    c.inc(5)
    first = aggregate.counter_deltas()
    ship = [d for d in first if d[0] == "t_ship_total"]
    assert ship and ship[0][2] == 5
    assert not [d for d in aggregate.counter_deltas()
                if d[0] == "t_ship_total"]   # nothing new since last ack
    c.inc(2)
    again = [d for d in aggregate.counter_deltas()
             if d[0] == "t_ship_total"]
    assert again[0][2] == 2
    before = telemetry.counter("t_absorb_total").value
    aggregate.absorb_counter_deltas([("t_absorb_total", {}, 3)])
    assert telemetry.counter("t_absorb_total").value == before + 3


# ---------------------------------------------------------------------------
# StepClock: phases, verdicts, report
# ---------------------------------------------------------------------------

def test_stepclock_phase_accounting():
    clock = stepclock.StepClock(window=8)
    clock.note("data_wait", 0.05)          # between-steps note → pending
    clock.begin_step()
    clock.note("comms", 0.02)
    clock.note("h2d", 0.001)
    clock.end_step()
    s = clock.summary()
    assert s["steps"] == 1
    rec = s["phases"]
    assert rec["data_wait"]["median"] == pytest.approx(0.05)
    assert rec["comms"]["median"] == pytest.approx(0.02)
    # unattributed remainder lands in compute; phases sum ~ total
    total = s["phases"]["total"]["median"]
    parts = sum(rec[p]["median"] for p in stepclock.PHASES)
    assert parts == pytest.approx(total, rel=1e-6)
    with pytest.raises(ValueError):
        clock.note("warp", 1.0)


def test_stepclock_abandoned_step_discarded():
    clock = stepclock.StepClock(window=8)
    clock.begin_step()          # never ended (amp overflow-skip path)
    clock.begin_step()
    clock.end_step()
    assert clock.steps == 1
    clock.end_step()            # double end: no-op
    assert clock.steps == 1


def test_verdict_input_bound_through_dataloader():
    """A decode-throttled run must label input-bound: the DataLoader's
    fetch spans feed data_wait, dwarfing the tiny model's compute."""
    class SlowDS(gluon.data.ArrayDataset):
        def __getitem__(self, idx):
            time.sleep(0.01)
            return super().__getitem__(idx)

    ds = SlowDS(mx.nd.array(np.random.randn(16, 3).astype(np.float32)))
    net = gluon.nn.Dense(2, in_units=3)
    net.initialize()
    tr = gluon.Trainer(net.collect_params(), "sgd", {"learning_rate": 0.1})

    def epoch():
        for x in gluon.data.DataLoader(ds, batch_size=4):
            with autograd.record():
                loss = (net(x) ** 2).sum()
            loss.backward()
            tr.step(4)

    epoch()     # compiles happen here, outside the measured window: the
    #             first two of only four steps would otherwise be compile
    telemetry.enable()
    epoch()
    assert telemetry.STEP_CLOCK.verdict() == "input-bound"
    rep = telemetry.report()
    assert "verdict: input-bound" in rep
    assert "data_wait" in rep
    # the labeled histogram series recorded every step
    h = telemetry.REGISTRY.get("mxnet_step_phase_seconds",
                               labels={"phase": "data_wait"})
    assert h is not None and h.count == 4


def test_verdict_comms_bound_through_chaos_delay():
    """A comms-heavy run must label comms-bound: chaos latency injection
    at kvstore.allreduce (the dist store's per-key reduce) dominates."""
    from mxnet_tpu.resilience import chaos
    telemetry.enable()
    kv = mx.kv.create("dist_tpu_sync")
    kv.set_bucket_size(0)      # per-key pushes cross the allreduce site
    chaos.inject("kvstore.allreduce", kind="delay", times=0, delay_s=0.02)
    try:
        net = gluon.nn.Dense(2, in_units=3)
        net.initialize()
        tr = gluon.Trainer(net.collect_params(), "sgd",
                           {"learning_rate": 0.1}, kvstore=kv)
        x = mx.nd.array(np.ones((2, 3), np.float32))
        for _ in range(3):
            with autograd.record():
                loss = (net(x) ** 2).sum()
            loss.backward()
            tr.step(2)
    finally:
        chaos.clear()
    assert telemetry.STEP_CLOCK.verdict() == "comms-bound"
    assert "verdict: comms-bound" in telemetry.report()


def test_report_cli_merges_and_reports(tmp_path):
    telemetry.enable()
    with telemetry.span("cliwork", "test"):
        pass
    aggregate.export_snapshot(directory=str(tmp_path))
    aggregate.set_rank(1)
    aggregate.export_snapshot(directory=str(tmp_path))
    trace_out = tmp_path / "merged_trace.json"
    prom_out = tmp_path / "merged.prom"
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "telemetry_report.py"),
         "--dir", str(tmp_path), "--trace", str(trace_out),
         "--prom", str(prom_out)],
        capture_output=True, text=True, timeout=60)
    assert res.returncode == 0, res.stderr[-500:]
    assert "2 rank(s)" in res.stdout
    assert "job verdict:" in res.stdout
    with open(trace_out) as f:
        trace = json.load(f)
    assert {e.get("pid") for e in trace["traceEvents"]
            if e.get("ph") == "X"} == {0, 1}
    assert "mxnet_step_phase_seconds" in prom_out.read_text()
    # --json mode
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "tools", "telemetry_report.py"),
         "--dir", str(tmp_path), "--json"],
        capture_output=True, text=True, timeout=60)
    assert res.returncode == 0
    assert [r["rank"] for r in json.loads(res.stdout)["ranks"]] == [0, 1]


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def _dumps_in(d):
    if not os.path.isdir(d):
        return []
    return sorted(f for f in os.listdir(d)
                  if f.startswith("flightrec-") and f.endswith(".json"))


def test_flightrec_dump_contents(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_FLIGHTREC_DIR", str(tmp_path))
    flightrec._reset_dump_cap_for_test()
    telemetry.enable()
    with telemetry.span("pre-crash", "test"):
        pass
    flightrec.note("about_to_die", step=3)
    try:
        raise RuntimeError("synthetic failure")
    except RuntimeError as e:
        path = flightrec.dump("test.reason", exc=e)
    assert path and os.path.exists(path)
    with open(path) as f:
        rec = json.load(f)
    assert rec["reason"] == "test.reason"
    assert rec["rank"] == 0 and rec["pid"] == os.getpid()
    assert rec["exception"]["type"] == "RuntimeError"
    assert "synthetic failure" in rec["exception"]["message"]
    assert any(e["name"] == "pre-crash" for e in rec["spans"])
    assert any(c["event"] == "about_to_die" for c in rec["breadcrumbs"])
    assert "MXNET_FLIGHTREC" in rec["config"]
    assert "armed_sites" in rec["chaos"]
    assert any(m["name"] == "mxnet_op_dispatch_total"
               for m in rec["metrics"])


def test_flightrec_dump_cap(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_FLIGHTREC_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_FLIGHTREC_MAX_DUMPS", "2")
    flightrec._reset_dump_cap_for_test()
    assert flightrec.dump("one") and flightrec.dump("two")
    assert flightrec.dump("three") is None
    assert len(_dumps_in(str(tmp_path))) == 2
    flightrec._reset_dump_cap_for_test()


def test_flightrec_disabled_no_dump(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_FLIGHTREC_DIR", str(tmp_path))
    monkeypatch.setenv("MXNET_FLIGHTREC", "0")
    flightrec._reset_dump_cap_for_test()
    assert flightrec.dump("nope") is None
    assert _dumps_in(str(tmp_path)) == []


def test_flightrec_unhandled_exception_subprocess(tmp_path):
    res = _subprocess(
        "import mxnet_tpu\nraise RuntimeError('chaos-lane death')",
        env={"MXNET_FLIGHTREC_DIR": str(tmp_path)})
    assert res.returncode == 1
    assert "chaos-lane death" in res.stderr   # excepthook chains through
    dumps = _dumps_in(str(tmp_path))
    assert len(dumps) == 1 and "exception.RuntimeError" in dumps[0]
    with open(tmp_path / dumps[0]) as f:
        rec = json.load(f)
    assert rec["exception"]["message"] == "chaos-lane death"


def test_flightrec_chaos_exit_subprocess(tmp_path):
    """chaos 'exit' is os._exit — no excepthook, no atexit.  The dump must
    happen INSIDE chaos.hit, and it also exports the telemetry shard so a
    dead rank still appears in the merged trace."""
    code = """
import mxnet_tpu as mx
from mxnet_tpu import telemetry
from mxnet_tpu.resilience import chaos
telemetry.enable()
with telemetry.span('doomed-work', 'test'):
    pass
chaos.inject('trainer.step', kind='exit', times=1)
chaos.hit('trainer.step')
raise AssertionError('unreachable')
"""
    res = _subprocess(code, env={"MXNET_FLIGHTREC_DIR": str(tmp_path),
                                 "MXNET_TELEMETRY_DIR": str(tmp_path)})
    assert res.returncode == 1
    dumps = _dumps_in(str(tmp_path))
    assert len(dumps) == 1 and "chaos.exit.trainer.step" in dumps[0]
    with open(tmp_path / dumps[0]) as f:
        rec = json.load(f)
    assert any(e["name"] == "doomed-work" for e in rec["spans"])
    assert rec["chaos"]["faults_fired"] == 1
    # the dying rank's telemetry shard was exported too
    snaps = aggregate.load_snapshots(str(tmp_path))
    assert len(snaps) == 1
    assert any(e["name"] == "doomed-work" for e in snaps[0]["events"])


def test_flightrec_deadline_dump(tmp_path, monkeypatch):
    from mxnet_tpu.resilience import Deadline, KVStoreTimeoutError
    monkeypatch.setenv("MXNET_FLIGHTREC_DIR", str(tmp_path))
    flightrec._reset_dump_cap_for_test()
    with pytest.raises(KVStoreTimeoutError):
        Deadline(timeout_s=0.05, site="test.site").call(time.sleep, 5)
    dumps = _dumps_in(str(tmp_path))
    assert len(dumps) == 1 and "deadline.test.site" in dumps[0]


@pytest.mark.skipif(not hasattr(signal, "SIGUSR2"), reason="no SIGUSR2")
def test_flightrec_sigusr2_on_demand(tmp_path, monkeypatch):
    monkeypatch.setenv("MXNET_FLIGHTREC_DIR", str(tmp_path))
    flightrec._reset_dump_cap_for_test()
    flightrec.install()   # idempotent; installed at import in main thread
    os.kill(os.getpid(), signal.SIGUSR2)
    deadline = time.time() + 5
    while time.time() < deadline and not _dumps_in(str(tmp_path)):
        time.sleep(0.01)
    dumps = _dumps_in(str(tmp_path))
    assert len(dumps) == 1 and "sigusr2" in dumps[0]
    # the process keeps running (this assertion executing is the proof)
