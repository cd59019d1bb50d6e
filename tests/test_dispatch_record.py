"""The dispatch record (telemetry.stepclock.DISPATCHES): one record a
``TrainStep`` dispatch, stamped by the program whatever the telemetry flag
says; the counters it banks, the line a slow dispatch gets, and the
step clock and tracer fed from the same stamps when telemetry is on."""

import logging
import time

import numpy as np
import pytest

import mxnet_tpu as mx
from mxnet_tpu import gluon, parallel, telemetry
from mxnet_tpu.telemetry import stepclock
from test_step_names import tiny_batches, tiny_step

FIELDS = ("seq", "owner", "steps", "t_begin", "bookkeeping_s", "h2d_s",
          "enqueue_s", "writeback_s", "t_enqueued", "fed", "built",
          "t_fetch", "fetch_wait_s", "t_ready", "late_fetch", "interval_s",
          "caller_s")
LONG = 20       # scanned steps of a dispatch that outlasts its enqueue


def dense_step(n_micro=1):
    net = gluon.nn.Dense(4, in_units=8)
    net.initialize()
    return parallel.TrainStep(net, lambda out, y: ((out - y) ** 2).mean(),
                              "sgd", {"learning_rate": 0.1}, n_micro=n_micro)


def dense_batch():
    rng = np.random.RandomState(0)
    return (mx.nd.array(rng.randn(8, 8).astype("float32")),
            mx.nd.array(rng.randn(8, 4).astype("float32")))


def mine(step):
    return [r for r in stepclock.DISPATCHES if r.owner == step._owner]


def value(name, **labels):
    m = telemetry.REGISTRY.get(name, labels=labels or None)
    return 0 if m is None else m.value


@pytest.fixture(scope="module")
def bert():
    """A built tiny BERT step and one batch of it (a row of the stack)."""
    step = tiny_step()
    tokens, labels = tiny_batches()
    step.run(tokens, labels).asnumpy()
    return step, tokens[0], labels[0]


# -- the record ---------------------------------------------------------------

def test_every_dispatch_leaves_a_record_with_every_field():
    assert not telemetry.enabled()
    step, (x, y) = dense_step(), dense_batch()
    for _ in range(3):
        step(x, y).asnumpy()
    losses = step.run(x, y, steps=2)            # not fetched
    records = mine(step)
    assert [r.steps for r in records] == [1, 1, 1, 2]
    assert [r.seq for r in records] == sorted(r.seq for r in records)
    assert [r.built for r in records] == [True, False, False, True]
    for r in records:
        assert tuple(r.as_dict()) == FIELDS == stepclock.Dispatch.__slots__
        for phase in stepclock.HOST_PHASES:
            assert getattr(r, phase + "_s") >= 0
        assert r.t_begin < r.t_enqueued
        assert r.bookkeeping_s + r.h2d_s + r.enqueue_s + r.writeback_s \
            <= time.perf_counter() - r.t_begin
    for r in records[:3]:
        assert r.t_enqueued <= r.t_fetch <= r.t_ready
        assert r.fetch_wait_s == r.t_ready - r.t_fetch
    assert records[0].interval_s is None        # nothing fetched before it
    for before, r in zip(records, records[1:3]):
        assert r.interval_s == r.t_ready - before.t_ready
        assert 0 <= r.caller_s <= r.interval_s
    unfetched = records[3]
    assert (unfetched.t_fetch, unfetched.fetch_wait_s, unfetched.t_ready,
            unfetched.late_fetch, unfetched.interval_s) == (None,) * 5
    losses.asnumpy()
    assert unfetched.t_ready is not None
    stamped = unfetched.t_ready
    losses.asnumpy()                            # a fetch stamps once
    assert unfetched.t_ready == stamped


def test_a_program_lowered_first_is_built_by_its_first_dispatch(bert):
    _, tokens, labels = bert
    step = tiny_step()
    step.lowered(tokens, labels, steps=2)
    step.run(tokens, labels, steps=2).asnumpy()
    step.run(tokens, labels, steps=2).asnumpy()
    assert [r.built for r in mine(step)] == [True, False]


def test_fed_says_whether_the_device_still_had_work_queued(bert):
    step, tokens, labels = bert
    step.run(tokens, labels, steps=LONG).asnumpy()      # build this length
    unfed = value("mxnet_trainstep_unfed_total")
    first = step.run(tokens, labels, steps=LONG)        # the queue is empty
    behind = step.run(tokens, labels, steps=LONG)       # behind `first`
    first.asnumpy()
    behind.asnumpy()                                    # drains the queue
    after = step.run(tokens, labels, steps=LONG)
    after.asnumpy()
    assert [r.fed for r in mine(step)[-3:]] == [False, True, False]
    assert value("mxnet_trainstep_unfed_total") == unfed + 2
    # a TrainStep's first dispatch is not fed, whatever another one queued
    other = dense_step()
    step.run(tokens, labels, steps=LONG)
    other(*dense_batch())
    assert mine(other)[0].fed is False


def test_the_fetch_stamps_its_wait_and_a_late_fetch_says_so(bert):
    step, tokens, labels = bert
    step.run(tokens, labels, steps=LONG).asnumpy()
    waited = value("mxnet_trainstep_fetch_wait_seconds")
    step.run(tokens, labels, steps=LONG).asnumpy()      # fetched at once
    losses = step.run(tokens, labels, steps=LONG)
    losses.wait_to_read()                               # ready, then fetched
    losses.asnumpy()
    at_once, late = mine(step)[-2:]
    assert at_once.late_fetch is False and late.late_fetch is True
    assert at_once.fetch_wait_s > 20 * late.fetch_wait_s
    assert at_once.fetch_wait_s > 0.5 * at_once.interval_s
    # the caller's wait_to_read is the caller's time, not the fetch's
    assert late.caller_s > 0.5 * late.interval_s
    assert value("mxnet_trainstep_fetch_wait_seconds") == pytest.approx(
        waited + at_once.fetch_wait_s + late.fetch_wait_s)


def test_the_ring_keeps_the_last_256():
    assert stepclock.DISPATCHES.maxlen == 256
    step, (x, y) = dense_step(), dense_batch()
    for _ in range(260):
        step(x, y)
    assert len(stepclock.DISPATCHES) == 256
    assert len(mine(step)) == 256
    seqs = [r.seq for r in stepclock.DISPATCHES]
    assert seqs == list(range(seqs[0], seqs[0] + 256))


# -- the counters the record banks -------------------------------------------

def test_the_counters_grow_with_telemetry_off():
    assert not telemetry.enabled()
    names = {p: ("mxnet_trainstep_host_seconds", {"phase": p})
             for p in stepclock.HOST_PHASES}
    names.update({k: (k, {}) for k in (
        "mxnet_trainstep_fetch_wait_seconds", "mxnet_trainstep_unfed_total",
        "mxnet_trainstep_resolve_seconds",
        "mxnet_sharding_step_dispatches_total",
        "mxnet_sharding_retraces_total",
        "mxnet_trainstep_microbatches_total")})
    before = {k: value(n, **lb) for k, (n, lb) in names.items()}
    step, (x, y) = dense_step(n_micro=2), dense_batch()
    for _ in range(2):
        step.run(x, y, steps=3).asnumpy()
    grew = {k: value(n, **lb) - before[k] for k, (n, lb) in names.items()}
    records = mine(step)
    for phase in stepclock.HOST_PHASES:
        assert grew[phase] == pytest.approx(
            sum(getattr(r, phase + "_s") for r in records))
    assert grew["mxnet_trainstep_fetch_wait_seconds"] == pytest.approx(
        sum(r.fetch_wait_s for r in records))
    assert grew["mxnet_trainstep_resolve_seconds"] > 0
    assert grew["mxnet_trainstep_unfed_total"] == 2
    assert grew["mxnet_sharding_step_dispatches_total"] == 2
    assert grew["mxnet_sharding_retraces_total"] == 1
    assert grew["mxnet_trainstep_microbatches_total"] == 2 * 2 * 3
    text = telemetry.to_prometheus()
    for name, _labels in names.values():
        assert name in text


def test_resolve_from_shapes_alone_is_counted_too():
    step, (x, _y) = dense_step(), dense_batch()
    step.net(x)                                 # every shape known
    before = value("mxnet_trainstep_resolve_seconds")
    step._resolve(None)
    assert value("mxnet_trainstep_resolve_seconds") > before


class _Device:
    def __init__(self, in_use):
        self._in_use = in_use

    def memory_stats(self):
        return None if self._in_use is None \
            else {"bytes_in_use": self._in_use, "peak_bytes_in_use": 1 << 40}


class _Shard:
    def __init__(self, device, nbytes):
        self.device = device
        self.data = np.zeros(nbytes, np.uint8)


class _Array:
    def __init__(self, *shards):
        self.addressable_shards = shards


class _Mesh:
    def __init__(self, *devices):
        self.devices = list(devices)


def test_device_bytes_are_read_on_the_fullest_device_at_a_build(monkeypatch):
    name = "mxnet_trainstep_device_bytes"
    # the CPU gives no memory_stats: a real dispatch that builds sets nothing
    step, (x, y) = dense_step(), dense_batch()
    step(x, y).asnumpy()
    assert mine(step)[0].built
    assert telemetry.REGISTRY.get(name, labels={"kind": "in_use"}) is None
    assert name not in telemetry.to_prometheus()
    registry = telemetry.MetricsRegistry()      # the planted readings' own
    monkeypatch.setattr(telemetry.metrics, "REGISTRY", registry)
    parallel._bank_device_bytes(_Mesh(_Device(None), _Device(None)), ())
    assert registry.get(name, labels={"kind": "in_use"}) is None
    light, full = _Device(1000), _Device(5000)
    arrays = (_Array(_Shard(light, 300), _Shard(full, 700)),
              _Array(_Shard(full, 2000)), _Array(_Shard(light, 100)))
    parallel._bank_device_bytes(_Mesh(light, full), arrays)
    assert registry.get(name, labels={"kind": "in_use"}).value == 5000
    assert registry.get(name, labels={"kind": "arguments"}).value == 2700


# -- the slow dispatch says so -------------------------------------------------

def _planted(intervals, steps=2, wait_share=0.9, owner=-1):
    """Fetched records whose ready-to-ready intervals are ``intervals``,
    of a loop that dispatches and then fetches: the host's phases take
    1 ms a dispatch, the fetch waits ``wait_share`` of what is left of the
    interval and the caller takes the rest."""
    ring, lines, t = [], [], 100.0
    for i, interval in enumerate([0.5] + list(intervals)):
        rec = stepclock.Dispatch(i, owner, steps, t)
        spans, at = {}, t
        for phase in stepclock.HOST_PHASES:
            spans[phase] = (at, at + 0.00025)
            at += 0.00025
        for name, (t0, t1) in spans.items():
            setattr(rec, name + "_s", t1 - t0)
        rec.t_enqueued, rec.fed, rec.built = at, i > 0, i == 0
        ring.append(rec)
        t_fetch = t + 0.001 + (1 - wait_share) * (interval - 0.001)
        t += interval
        lines.append(stepclock.close_fetch(rec, t_fetch, t, False, ring=ring))
    return ring, lines


def test_a_steady_run_gets_no_line():
    ring, lines = _planted([0.7 + 0.01 * (i % 5) for i in range(40)])
    assert lines == [None] * 41
    assert [r.interval_s for r in ring[1:]] == pytest.approx(
        [0.7 + 0.01 * (i % 5) for i in range(40)])


def test_an_interval_at_three_medians_gets_one_line_naming_what_grew():
    steady = [0.7] * 12
    ring, lines = _planted(steady + [2.1] + steady)
    said = [ln for ln in lines if ln]
    assert len(said) == 1 and lines[13] == said[0]
    assert "took 2.1000 s ready to ready, 3.00x the median 0.7000 s of " \
        "the last 12" in said[0]
    assert "fetch_wait grew to 1.8891 s from 0.6291 s" in said[0]
    assert "fed=True built=False" in said[0]
    # the same interval spent by the caller before the fetch names the caller
    ring, lines = _planted(steady + [2.1], wait_share=0.0)
    assert "caller grew to 2.0990 s from 0.6990 s" in lines[-1]
    # under eight intervals of the same steps there is nothing to go by
    ring, lines = _planted([0.7] * 7 + [2.1])
    assert lines[-1] is None
    ring, lines = _planted([0.7] * 8 + [2.1])
    assert lines[-1] is not None
    # 1.5 medians is the line: at it nothing, over it a line
    assert _planted([0.7] * 8 + [1.05])[1][-1] is None
    assert _planted([0.7] * 8 + [1.06])[1][-1] is not None


def test_other_lengths_and_other_steps_are_not_compared():
    ring, _ = _planted([0.7] * 12)
    slow = stepclock.Dispatch(99, -1, 4, ring[-1].t_ready)   # 4 steps: none
    for phase in stepclock.HOST_PHASES:
        setattr(slow, phase + "_s", 0.00025)
    ring.append(slow)
    assert stepclock.close_fetch(slow, slow.t_begin + 0.001,
                                 slow.t_begin + 5.0, False, ring=ring) is None
    assert slow.interval_s == pytest.approx(5.0)
    other = stepclock.Dispatch(100, -2, 2, slow.t_ready)     # another step's
    for phase in stepclock.HOST_PHASES:
        setattr(other, phase + "_s", 0.00025)
    ring.append(other)
    assert stepclock.close_fetch(other, other.t_begin + 0.001,
                                 other.t_begin + 5.0, False,
                                 ring=ring) is None
    assert other.interval_s is None


def test_trainstep_logs_the_line_once(caplog):
    step, (x, y) = dense_step(), dense_batch()
    with caplog.at_level(logging.WARNING, logger="mxnet_tpu.trainstep"):
        for _ in range(12):
            step(x, y).asnumpy()
        caplog.clear()      # a millisecond step on a shared CPU may jitter
        losses = step(x, y)
        time.sleep(0.5)                     # the caller's own time
        losses.asnumpy()
        assert len(caplog.records) == 1
        record = caplog.records[0]
        assert record.name == "mxnet_tpu.trainstep"
        assert record.levelno == logging.WARNING
        assert "caller grew to 0.5" in record.getMessage()
        caplog.clear()
        losses.asnumpy()                    # fetched already: no second line
        assert caplog.records == []


# -- telemetry on: the same stamps feed the step clock and the tracer --------

def test_with_telemetry_on_the_record_feeds_the_clock_and_the_tracer():
    step, (x, y) = dense_step(), dense_batch()
    step(x, y).asnumpy()
    telemetry.enable()
    try:
        telemetry.clear()
        for _ in range(3):
            step(x, y).asnumpy()
        records = mine(step)[-3:]
        window = list(telemetry.STEP_CLOCK._window)
        assert len(window) == 3
        for rec, row in zip(records, window):
            assert row["h2d"] == rec.h2d_s
            assert row["enqueue"] == pytest.approx(
                rec.bookkeeping_s + rec.enqueue_s + rec.writeback_s)
            assert row["data_wait"] == row["comms"] == row["optimizer"] == 0
            assert row["compute"] == pytest.approx(
                row["total"] - row["h2d"] - row["enqueue"])
        # a step runs from the writeback before to this one's: the gap (the
        # fetch, the caller) is what the clock calls compute
        assert window[1]["total"] == pytest.approx(
            records[1].t_enqueued - records[0].t_enqueued, abs=1e-4)
        events = [e for e in telemetry.get_tracer().events()
                  if e["name"].startswith("trainstep.")]
        assert [e["name"] for e in events] == [
            "trainstep.bookkeeping", "trainstep.h2d", "trainstep.enqueue",
            "trainstep.writeback", "trainstep.fetch"] * 3
        assert {e["cat"] for e in events} == {"trainstep"}
        for rec, mine_ in zip(records, zip(*[iter(events)] * 5)):
            durs = [e["dur"] / 1e6 for e in mine_]
            assert durs == pytest.approx(
                [rec.bookkeeping_s, rec.h2d_s, rec.enqueue_s,
                 rec.writeback_s, rec.fetch_wait_s], abs=2e-6)
        text = telemetry.report()
        lines = text.splitlines()
        assert lines[0] == "step-time attribution (last 3 step(s)):"
        assert lines[1].split() == ["phase", "median_ms", "p90_ms",
                                    "mean_ms", "share"]
        assert [ln.split()[0] for ln in lines[2:9]] == \
            list(stepclock.PHASES) + ["total"]
        by_phase = {ln.split()[0]: float(ln.split()[1]) for ln in lines[2:9]}
        assert by_phase["h2d"] > 0 and by_phase["enqueue"] > 0
        assert lines[9].startswith("verdict: ")
        assert "mxnet_sharding_step_dispatches_total = " in text
    finally:
        telemetry.disable()
        telemetry.clear()


# -- what the record costs ------------------------------------------------------

def test_the_host_cost_of_a_dispatch_around_a_stubbed_program(monkeypatch):
    """Everything ``_dispatch`` and the fetch do on the host but the
    transfers and the program: the record, its counters, the look at the
    dispatch before, the median over a full ring.  Tens of microseconds
    against dispatches of 420-700 ms."""
    import jax
    step, (x, y) = dense_step(), dense_batch()
    for _ in range(260):                        # a full ring of peers
        step(x, y).asnumpy()
    losses = step(x, y)._data
    outputs = (tuple(p._data._data for p in step._params),
               tuple(s._data for s in step._state_nds), losses, {})
    scalars = (jax.random.PRNGKey(0), np.float32(1.0),
               np.zeros((len(step._trainable),), np.float32),
               np.float32(1.0))
    monkeypatch.setattr(jax, "device_put", lambda value, _sharding: value)

    def program(*_arguments):
        return outputs

    def fifty():
        t0 = time.thread_time()
        for _ in range(50):
            step._dispatch(program, lambda: scalars, x, y, False,
                           1).asnumpy()
        return (time.thread_time() - t0) / 50

    # the stubbed dispatch does all its work on this thread, so the
    # thread's CPU time is its cost, less the time the suite's other
    # workers hold the core; the best of many short batches is one that
    # shared the core's caches least
    best = min(fifty() for _ in range(20))
    assert best < 100e-6, f"{best * 1e6:.1f} us a dispatch"
