"""mx.profiler — facade over mx.telemetry + jax.profiler.

Rebuild of src/profiler/* (N20) + python/mxnet/profiler.py (P20).  The
reference hooks the engine's ExecuteOprBlock to emit Chrome-trace JSON and
per-op aggregates; here the host-side timeline + per-op table come from
mxnet_tpu.telemetry (span tracer + dispatch ledger fed by ops.registry),
and the device timeline (fusion boundaries, HLO ops) from the XLA trace
jax.profiler writes alongside (SURVEY §5.1 host/device split).

API parity: set_config, set_state('run'/'stop'), start/stop, dump, dumps,
scope/Task/Counter/Marker objects, pause/resume.  ``dump()`` writes genuine
Chrome-trace JSON (the reference profile_output); the human table moved to
``dumps(format="table")`` (default) with ``format="json"`` for machines.

State discipline: the XLA trace lifecycle is tracked in ``xla_trace``
*independently* of ``running`` — ``pause()`` stops host-side recording but
keeps the device trace open, and a later ``stop()`` still closes it.
"""

from __future__ import annotations

import contextlib
import json
import os
import time

from . import telemetry
from .base import MXNetError

__all__ = ["set_config", "set_state", "start", "stop", "pause", "resume",
           "dump", "dumps", "scope", "Task", "Frame", "Counter", "Marker"]

_state = {
    "running": False,
    "filename": "profile.json",
    "trace_dir": None,
    "xla_trace": False,   # device trace open — independent of `running`
    "tel_owner": False,   # start() flipped telemetry on, so stop() turns it off
}


def set_config(filename="profile.json", profile_all=False, profile_symbolic=False,
               profile_imperative=False, profile_memory=False, profile_api=False,
               aggregate_stats=True, continuous_dump=False, **kwargs):  # noqa: ARG001
    _state["filename"] = filename
    _state["trace_dir"] = os.path.splitext(filename)[0] + "_xla_trace"
    telemetry.ledger.set_aggregate_stats(aggregate_stats)


def is_running():
    return _state["running"]


def set_state(state="stop", profile_process="worker"):  # noqa: ARG001
    if state == "run":
        start()
    else:
        stop()


def start(profile_process="worker"):  # noqa: ARG001
    if _state["running"]:
        return
    _state["running"] = True
    # fresh profiling session: drop buffered spans AND ledger rows so dump()
    # covers one window (the reference start() resets its aggregates too)
    telemetry.clear()
    _state["tel_owner"] = not telemetry.enable()
    if not _state["xla_trace"]:
        trace_dir = _state["trace_dir"] or "profile_xla_trace"
        try:
            import jax
            jax.profiler.start_trace(trace_dir)
            _state["xla_trace"] = True
        except Exception:
            _state["xla_trace"] = False


def stop(profile_process="worker"):  # noqa: ARG001
    _state["running"] = False
    # tel_owner alone encodes ownership: if telemetry was already on at
    # start() (env switch or user enable), tel_owner is False and we leave it
    if _state["tel_owner"]:
        telemetry.disable()
        _state["tel_owner"] = False
    if _state["xla_trace"]:
        # closes the device trace even after a pause() (running already False)
        _state["xla_trace"] = False
        try:
            import jax
            jax.profiler.stop_trace()
        except Exception:
            pass


def pause(profile_process="worker"):  # noqa: ARG001
    """Suspend host-side recording; the XLA trace stays open so resume()
    continues into the same device timeline."""
    _state["running"] = False
    if _state["tel_owner"]:
        telemetry.disable()


def resume(profile_process="worker"):  # noqa: ARG001
    _state["running"] = True
    if _state["tel_owner"]:
        telemetry.enable()


def record_op(name, seconds):
    """Feed the per-op aggregate ledger (the ExecuteOprBlock hook analog;
    ops.registry now reports through telemetry.record_dispatch directly)."""
    telemetry.ledger.record_op(name, seconds)


def _ledger_rows(reset=False):
    snap = telemetry.ledger.snapshot(reset=reset)
    return sorted(snap.items(), key=lambda kv: -kv[1][1])


def _aggregate_dict(rows):
    """Ledger rows as the machine-readable aggregate schema (shared by
    dumps(format="json") and dump()'s otherData.opAggregates)."""
    return {
        name: {"calls": cnt, "total_ms": tot * 1e3, "min_ms": mn * 1e3,
               "max_ms": mx * 1e3, "avg_ms": tot / cnt * 1e3}
        for name, (cnt, tot, mn, mx) in rows}


def dumps(reset=False, format="table"):  # noqa: A002
    """Aggregate per-op stats (reference aggregate_stats.cc output).

    format="table" — the human-readable text table (default);
    format="json"  — machine-readable {name: {calls, total_ms, ...}}.
    """
    if format == "json":
        return json.dumps(_aggregate_dict(_ledger_rows(reset)),
                          indent=2, sort_keys=True)
    if format != "table":
        raise MXNetError(f"unknown dumps format {format!r}: "
                         "expected 'table' or 'json'")
    lines = [f"{'Name':<40}{'Calls':>8}{'Total(ms)':>12}{'Min(ms)':>10}"
             f"{'Max(ms)':>10}{'Avg(ms)':>10}"]
    lines.append("-" * 90)
    for name, (cnt, tot, mn, mx) in _ledger_rows(reset):
        lines.append(f"{name:<40}{cnt:>8}{tot*1e3:>12.3f}{mn*1e3:>10.3f}"
                     f"{mx*1e3:>10.3f}{tot/cnt*1e3:>10.3f}")
    return "\n".join(lines)


def dump(finished=True, profile_process="worker"):  # noqa: ARG001
    """Write the host timeline as Chrome-trace JSON (chrome://tracing /
    Perfetto); the per-op aggregate ledger rides under otherData."""
    trace = telemetry.chrome_trace()
    trace.setdefault("otherData", {})["opAggregates"] = \
        _aggregate_dict(_ledger_rows())
    with open(_state["filename"], "w") as f:
        json.dump(trace, f)


@contextlib.contextmanager
def scope(name="<unk>"):
    """Profiling scope — one ``telemetry.span`` (the span tracer and, while
    a profiler session is open, the XLA trace's host plane, under the same
    name) plus a ledger row.  A cheap no-op (no jax import, no recording)
    when neither the profiler nor telemetry is active."""
    if not (_state["running"] or telemetry.enabled()):
        yield
        return
    t0 = time.perf_counter()
    try:
        with telemetry.span(f"scope:{name}", "scope"):
            yield
    finally:
        if _state["running"]:
            record_op(f"scope:{name}", time.perf_counter() - t0)


class Task:
    def __init__(self, name, domain=None):  # noqa: ARG002
        self.name = name
        self._t0 = None

    def start(self):
        self._t0 = time.perf_counter_ns()

    def stop(self):
        if self._t0 is None:
            return
        t1 = time.perf_counter_ns()
        if telemetry.enabled():
            telemetry.get_tracer().add_event(
                f"task:{self.name}", "task", self._t0, t1)
        if _state["running"]:
            record_op(f"task:{self.name}", (t1 - self._t0) / 1e9)


Frame = Task


class Counter:
    def __init__(self, name, domain=None, value=0):  # noqa: ARG002
        self.name = name
        self.value = value

    def set_value(self, value):
        self.value = value

    def increment(self, delta=1):
        self.value += delta

    def decrement(self, delta=1):
        self.value -= delta

    __iadd__ = lambda self, d: (self.increment(d), self)[1]
    __isub__ = lambda self, d: (self.decrement(d), self)[1]


class Marker:
    def __init__(self, name, domain=None):  # noqa: ARG002
        self.name = name

    def mark(self, scope="process"):  # noqa: ARG002
        telemetry.instant(f"marker:{self.name}", "marker")
        if _state["running"]:
            record_op(f"marker:{self.name}", 0.0)
