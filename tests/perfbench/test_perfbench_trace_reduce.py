"""perfbench/trace_reduce.py against a trace small enough to work by hand,
and against a recorded one (two steps of a traced run on the v5e, trimmed
by perfbench/tools/record_trace.py) whose values were worked out once with
plain loops below."""

import os

import pytest

from perfbench import trace_reduce as tr

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
RECORDED = os.path.join(ROOT, "perfbench", "testdata",
                        "trace_one_chip_two_steps.json.gz")


def _trace(dev_events, host_events, second_device=None):
    planes = [{"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_step", 0, 10 ** 6]]},
        {"name": "XLA Ops", "events": dev_events}]},
        {"name": "/host:CPU", "lines": [
            {"name": "python3", "events": host_events}]}]
    if second_device is not None:
        planes.insert(1, {"name": "/device:TPU:1", "lines": [
            {"name": "XLA Ops", "events": second_device}]})
    return {"planes": planes}


# a window of 1000 ns; a while from 100 to 700 spans two fusions, a kernel
# and an all-reduce; one more fusion runs alone from 800 to 900
HAND = [["while.1", 100, 600],
        ["fusion.1", 100, 100],
        ["branch_0_fun.7:tpu_custom_call", 200, 150],
        ["fusion.1", 400, 50],
        ["all-reduce.3", 500, 200],
        ["fusion.2", 800, 100],
        ["fusion.9", 1200, 50]]         # after the window: dropped
HOST = [["perfbench_window", 0, 1000], ["perfbench_enqueue", 0, 90],
        ["perfbench_fetch", 690, 200], ["other", 0, 1000]]


def test_hand_worked_busy_idle_and_names():
    r = tr.reduce(_trace(HAND, HOST))
    # busy: [100, 700] and [800, 900] = 700 ns of 1000
    assert r["busy_s"] == pytest.approx(700e-9)
    assert r["window_s"] == pytest.approx(1000e-9)
    assert r["idle_share_max"] == pytest.approx(0.3)
    ops = r["ops"]["/device:TPU:0"]
    # the while's own time: 600 - (100 + 150 + 50 + 200) = 100
    assert ops["while.1"] == [1, pytest.approx(100e-9)]
    assert ops["fusion.1"] == [2, pytest.approx(150e-9)]
    assert ops["all-reduce.3"] == [1, pytest.approx(200e-9)]
    assert "fusion.9" not in ops
    assert sum(t for _n, t in ops.values()) == pytest.approx(r["busy_s"])
    assert r["device_ops"][0] == ["all-reduce.3", pytest.approx(200e-9)]
    # gaps: 0-100 (enqueue covers 90 of it), 700-800 (fetch), 900-1000
    gaps = sorted((name, round(s * 1e9)) for name, s in r["idle_gaps"])
    assert gaps == [("host_other", 100), ("perfbench_enqueue", 100),
                    ("perfbench_fetch", 100)]


def test_hand_worked_kernel_and_all_reduce_sums():
    r = tr.reduce(_trace(HAND, HOST))
    kernel = tr.seconds_of(r, tr.is_pallas_call)
    assert kernel == pytest.approx(150e-9)
    assert tr.seconds_of(r, lambda n: n.startswith("all-reduce")) \
        == pytest.approx(200e-9)
    assert tr.seconds_of(r, lambda n: n.startswith("all-gather")) is None


def test_several_devices_average_busy_and_take_the_idlest():
    second = [["fusion.1", 0, 400], ["all-reduce.3", 400, 300]]
    r = tr.reduce(_trace(HAND, HOST, second_device=second))
    assert r["busy_s"] == pytest.approx(700e-9)          # both 700
    assert r["idle_share_max"] == pytest.approx(0.3)
    # the fullest device's all-reduce time
    assert tr.seconds_of(r, lambda n: n.startswith("all-reduce")) \
        == pytest.approx(300e-9)


def test_window_clips_an_event_that_straddles_it():
    r = tr.reduce(_trace([["fusion.1", 900, 300]], HOST))
    assert r["busy_s"] == pytest.approx(100e-9)


def test_without_a_window_span_the_device_events_bound_it():
    r = tr.reduce(_trace(HAND[:6], [["other", 0, 5000]]))
    assert r["window_s"] == pytest.approx(800e-9)        # 100 .. 900
    assert r["busy_s"] == pytest.approx(700e-9)


def test_no_device_op_is_an_error_not_a_zero():
    with pytest.raises(ValueError):
        tr.reduce(_trace([], HOST))
    with pytest.raises(ValueError):
        tr.reduce({"planes": [{"name": "/host:CPU", "lines": []}]})


def test_short_name_keeps_a_custom_calls_target():
    text = ('%branch_0_fun.97 = (bf16[32,12,512,64]{3,2,1,0}) custom-call('
            'bf16[32,12,512,64] %bitcast.1), '
            'custom_call_target="tpu_custom_call", operand_layout={}')
    assert tr.short_name(text) == "branch_0_fun.97:tpu_custom_call"
    assert tr.short_name("%fusion.12 = bf16[8]{0} fusion(bf16[8] %p.1), "
                         "kind=kLoop") == "fusion.12"
    assert tr.short_name("perfbench_window") == "perfbench_window"


# -- the recorded trace --------------------------------------------------------
# BENCHMARK.json's first cell on one TPU v5e (PR 26, seed 104): the first 280 ms of
# the traced window, two steps of one dispatch.  Worked out once with the
# plain loops of _by_hand below and by reading the file: one ``while`` (the
# scan over steps) of 255,870,263 ns spans nearly all of it, 11 events are
# at the top level, the step's 24 Pallas calls ran twice.

BUSY_NS = 255_876_109
KERNEL_NS = 27_672_203


def _by_hand(events):
    """Busy time as the sum of the events no other event spans, and the
    kernel's time as the plain sum of its events' durations."""
    busy, end = 0, -1
    for _name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        if start >= end:
            busy, end = busy + dur, start + dur
    kernel = [d for n, _s, d in events if n.endswith(":tpu_custom_call")]
    return busy, len(kernel), sum(kernel)


def test_recorded_trace_reproduces_hand_worked_values():
    trace = tr.load_json(RECORDED)
    events = tr.device_ops(trace)["/device:TPU:0"]
    assert _by_hand(events) == (BUSY_NS, 48, KERNEL_NS)
    r = tr.reduce(trace)
    assert r["busy_s"] == pytest.approx(BUSY_NS / 1e9, rel=1e-12)
    assert r["window_s"] == pytest.approx(0.28)
    assert r["idle_share_max"] == pytest.approx(1 - BUSY_NS / 280e6)
    assert tr.seconds_of(r, tr.is_pallas_call) \
        == pytest.approx(KERNEL_NS / 1e9, rel=1e-12)
    assert tr.seconds_of(r, lambda n: n.startswith("all-reduce")) is None
    ops = r["ops"]["/device:TPU:0"]
    # the while keeps only what its body does not cover
    assert ops["while.8"] == [1, pytest.approx(124_850e-9)]
    assert sum(t for _n, t in ops.values()) == pytest.approx(BUSY_NS / 1e9)
    assert len([n for n in ops if n.endswith(":tpu_custom_call")]) == 24
    # the window opens with the first dispatch being enqueued: 21.99 ms
    assert r["idle_gaps"][0] == ["perfbench_enqueue",
                                 pytest.approx(0.02199416)]
    assert len(r["device_ops"]) == 10 and len(r["idle_gaps"]) == 10


def test_recorded_trace_through_the_kernel_metric_readers():
    from perfbench import run as harness
    bench, cell = harness.load_cell(
        harness.load_json(harness.ROOT, "BENCHMARK.json")
        ["workloads"][0]["name"])
    peak = harness.load_json(harness.HERE, "peaks.json")["TPU v5 lite"]
    run = {"trace": tr.reduce(tr.load_json(RECORDED)), "steps": 2,
           "cell": cell, "peak": peak}
    ms = harness.read_metric("flash_ms_per_step", run)
    assert ms == pytest.approx(KERNEL_NS / 2 / 1e6)
    # 12 layers x 77,309,411,328 FLOPs / 197e12 = 4.709 ms a step at peak
    share = harness.read_metric("flash_roofline", run)
    assert share == pytest.approx(100 * 4.709202e-3 / (KERNEL_NS / 2e9),
                                  rel=1e-5)
    assert 30 < share < 40
    assert harness.read_metric("device_idle_pct", run) \
        == pytest.approx(100 * (1 - BUSY_NS / 280e6))
    run["trace"] = None
    assert harness.read_metric("flash_ms_per_step", run) is None
    assert harness.read_metric("flash_roofline", run) is None
