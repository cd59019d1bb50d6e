"""The six readers of the program's dispatch record
(``mxnet_tpu.telemetry.stepclock.DISPATCHES`` and the ``mxnet_trainstep_*``
counters): each against a planted ring and registry, silent where the
program keeps nothing to read, declared last in BENCHMARK.json for every
cell, and fed by a tiny cell driven through the harness."""

import collections
import os
import time

import jax
import pytest

from mxnet_tpu import telemetry
from mxnet_tpu.telemetry import metrics as registry_module
from mxnet_tpu.telemetry import stepclock
from perfbench import counters_dispatch
from perfbench import run as harness

import perfbench_tiny as tiny

SEED = (1 << 31) + 11
SCAN = 3
NEW = {"host_ms_per_dispatch": ("ms", "entry", "train_tokens_per_s"),
       "host_busy_pct": ("%", "entry", "train_tokens_per_s"),
       "unfed_dispatches_in_window": ("count", "device",
                                      "train_tokens_per_s"),
       "dispatch_tail_ratio": ("ratio", "step program",
                               "train_tokens_per_s"),
       "step_resolve_s": ("s", "entry", "setup_s"),
       "hbm_unread_gb": ("GB", "step program", "train_tokens_per_s")}
# the benchmark's cells as PR 36 left them, in their order
CELLS = ["bert_base.train_s512", "bert_base.train_s128",
         "bert_large.train_s512", "bert_base.train_s512_dp4",
         "kanana_2_30b_a3b.train_s4096", "qwen3_next_80b_a3b.train_s8192",
         "ouro_2_6b.train_s4096"]
RING_READERS = ["host_ms_per_dispatch", "host_busy_pct",
                "unfed_dispatches_in_window", "dispatch_tail_ratio"]


def _record(seq, t_begin, t_fetch, t_ready, fed, host_ms):
    """A fetched dispatch whose four phases take ``host_ms`` together."""
    rec = stepclock.Dispatch(seq, 0, SCAN, t_begin)
    rec.bookkeeping_s = 0.1 * host_ms / 1e3
    rec.h2d_s = 0.6 * host_ms / 1e3
    rec.enqueue_s = 0.2 * host_ms / 1e3
    rec.writeback_s = 0.1 * host_ms / 1e3
    rec.t_enqueued = t_begin + host_ms / 1e3
    rec.fed, rec.built = fed, seq == 0
    rec.t_fetch, rec.t_ready = t_fetch, t_ready
    rec.fetch_wait_s, rec.late_fetch = t_ready - t_fetch, False
    return rec


@pytest.fixture
def planted(monkeypatch):
    """Two dispatches of set-up (slow, unfed, 50 ms of host time each), then
    a window of eight, one enqueued ahead of the one fetched: ready every
    0.5 s but for the sixth, which came 2.0 s after the fifth (4x); 20 ms of
    host time a dispatch; the window's first dispatch unfed; the fetches
    wait 0.45 s each, the slow one 1.95."""
    ring = collections.deque(maxlen=256)
    ring.append(_record(0, 0.0, 30.0, 31.0, False, 50.0))
    ring.append(_record(1, 40.0, 40.1, 41.0, False, 50.0))
    ready = [100.5, 101.0, 101.5, 102.0, 102.5, 104.5, 105.0, 105.5]
    begin = [100.0] + [r + 0.01 for r in ready[:-1]]
    for i, (t_begin, t_ready) in enumerate(zip(begin, ready)):
        wait = 1.95 if i == 5 else 0.45
        ring.append(_record(2 + i, t_begin, t_ready - wait, t_ready,
                            i > 0, 20.0))
    monkeypatch.setattr(stepclock, "DISPATCHES", ring)
    registry = registry_module.MetricsRegistry()
    registry.gauge("mxnet_trainstep_resolve_seconds").inc(18.9)
    for kind, nbytes in (("in_use", 10.014e9), ("arguments", 8.759e9)):
        registry.gauge("mxnet_trainstep_device_bytes",
                       labels={"kind": kind}).set(nbytes)
    monkeypatch.setattr(telemetry, "REGISTRY", registry)
    return {"steps": 8 * SCAN, "cell": {"traffic": {"scan_steps": SCAN}}}


def test_the_window_is_the_rings_last_dispatches(planted):
    records = counters_dispatch.window(planted)
    assert [r.seq for r in records] == list(range(2, 10))
    # the ring must hold the whole window, fetched
    assert counters_dispatch.window(dict(planted, steps=11 * SCAN)) is None
    assert counters_dispatch.window(dict(planted, steps=0)) is None
    stepclock.DISPATCHES[-1].t_ready = None
    assert counters_dispatch.window(planted) is None


@pytest.mark.parametrize("name, value", [
    ("host_ms_per_dispatch", 20.0),
    # 8 fetches waited 7 x 0.45 + 1.95 = 5.1 s of the 5.5 from the first
    # dispatch's begin to the last losses ready
    ("host_busy_pct", 100 * (1 - 5.1 / 5.5)),
    ("unfed_dispatches_in_window", 1),
    ("dispatch_tail_ratio", 4.0),
    ("step_resolve_s", 18.9),
    ("hbm_unread_gb", 1.255),
])
def test_a_reader_against_the_planted_record(planted, name, value):
    assert harness.read_metric(name, planted) == pytest.approx(value)


def test_set_ups_dispatches_are_left_out(planted):
    # with them the host time would read (2 x 50 + 8 x 20) / 10 = 26 ms and
    # three dispatches would count as unfed
    assert harness.read_metric("host_ms_per_dispatch", planted) != \
        pytest.approx(26.0)
    assert sum(1 for r in stepclock.DISPATCHES if not r.fed) == 3
    # the interval from set-up's last dispatch to the window's first (59.5 s)
    # is no interval of the window
    intervals = counters_dispatch.ready_intervals(
        counters_dispatch.window(planted))
    assert len(intervals) == 7 and max(intervals) == pytest.approx(2.0)


def test_a_steady_window_reads_one(planted):
    ring = stepclock.DISPATCHES
    for i, rec in enumerate(list(ring)[2:]):
        rec.t_ready = 100.5 + 0.5 * i
    assert harness.read_metric("dispatch_tail_ratio", planted) == \
        pytest.approx(1.0)
    # under three dispatches there is no median to speak of
    assert harness.read_metric(
        "dispatch_tail_ratio", dict(planted, steps=2 * SCAN)) is None


@pytest.mark.parametrize("name", list(NEW))
def test_a_reader_is_silent_where_the_program_keeps_nothing(
        planted, monkeypatch, name):
    """The parent of the PR that brought the record has no ring and no such
    counters; a CPU run has no ``mxnet_trainstep_device_bytes``."""
    monkeypatch.delattr(stepclock, "DISPATCHES")
    monkeypatch.setattr(telemetry, "REGISTRY",
                        registry_module.MetricsRegistry())
    assert harness.read_metric(name, planted) is None


def test_hbm_unread_needs_both_kinds(planted):
    registry = registry_module.MetricsRegistry()
    registry.gauge("mxnet_trainstep_device_bytes",
                   labels={"kind": "in_use"}).set(1e9)
    telemetry.REGISTRY = registry       # the fixture's monkeypatch undoes it
    assert harness.read_metric("hbm_unread_gb", planted) is None


def test_the_six_metrics_are_declared_last_for_every_cell():
    bench = tiny.bench()
    assert [w["name"] for w in bench["workloads"]] == CELLS
    assert [m["name"] for m in bench["per_layer"][-6:]] == list(NEW)
    end_to_end = {m["name"] for m in bench["end_to_end"]}
    layers = {m["layer"] for m in bench["per_layer"][:-6]}
    for m in bench["per_layer"][-6:]:
        unit, layer, moves = NEW[m["name"]]
        assert (m["unit"], m["better"], m["source"], m["layer"],
                m["moves"]) == (unit, "lower", "program_counter", layer,
                                moves)
        assert m["workloads"] == CELLS
        assert m["moves"] in end_to_end and m["layer"] in layers
        assert set(m) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
        assert os.path.exists(os.path.join(harness.HERE, "metrics",
                                           m["name"] + ".py"))


def test_a_tiny_cell_leaves_its_windows_records(capfd):
    cell = tiny.cell()
    result = harness.run_cell(tiny.bench(), cell, SEED, 0.5, None,
                              jax.devices(), tiny.PEAK,
                              start=time.perf_counter())
    assert result["correct"] is True
    scan = cell["traffic"]["scan_steps"]
    run = {"steps": result["attempted"], "cell": cell}
    records = counters_dispatch.window(run)
    assert len(records) == result["attempted"] // scan >= 1
    assert {r.steps for r in records} == {scan}
    assert len({r.owner for r in records}) == 1
    # set-up's two dispatches stand right before the window's
    ring = list(stepclock.DISPATCHES)
    before = ring[-len(records) - 2:-len(records)]
    assert [r.owner for r in before] == [records[0].owner] * 2
    assert [r.built for r in before] == [True, False]
    assert not any(r.built for r in records)
    # set-up fetched its dispatches before the window opened
    assert records[0].fed is False
    assert all(r.t_ready is not None for r in records)
    assert harness.read_metric("unfed_dispatches_in_window", run) >= 1
    assert harness.read_metric("host_ms_per_dispatch", run) > 0
    assert 0 < harness.read_metric("host_busy_pct", run) <= 100
    assert harness.read_metric("step_resolve_s", run) > 0
    assert harness.read_metric("hbm_unread_gb", run) is None    # the CPU
    if len(records) >= 3:
        assert harness.read_metric("dispatch_tail_ratio", run) >= 1.0
    capfd.readouterr()


def test_gap_report_names_a_gap_from_both_sides():
    """The recorded trace holds the benchmark's spans only; a program span
    planted over the benchmark's first enqueue names the window's longest
    gap, and the gaps with no program span over them read ``host_other``."""
    from perfbench import trace_reduce as tr
    from perfbench.tools import gap_report
    trace = tr.load_json(os.path.join(
        harness.HERE, "testdata", "trace_one_chip_two_steps.json.gz"))
    bare = gap_report.gaps(trace)
    assert [[bench, seconds] for seconds, _p, bench in bare] == \
        tr.reduce(trace)["idle_gaps"][:5]
    assert {program for _s, program, _b in bare} == {"host_other"}
    host = next(p for p in trace["planes"]
                if p["name"].startswith(tr.HOST_PLANE_PREFIX))
    first = min((e for e in tr.host_spans(trace)
                 if e[0] == "perfbench_enqueue"), key=lambda e: e[1])
    host["lines"].append({"name": "planted", "events": [
        ["trainstep.h2d", first[1], first[2]]]})
    named = gap_report.gaps(trace)
    assert named[0] == [bare[0][0], "trainstep.h2d", "perfbench_enqueue"]
    assert [row[0] for row in named] == [row[0] for row in bare]
