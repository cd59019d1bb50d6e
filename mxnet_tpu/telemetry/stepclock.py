"""Step-time attribution — where did this training step's wall time go?

Every profiling recipe ends with the same question: is the
run input-bound, comms-bound, or compute-bound?  ``StepClock`` answers it
continuously: instrumented chokepoints split every optimizer step into

- ``data_wait``  — blocking on the input pipeline (DataLoader batch fetch,
  noted between steps and folded into the step they fed);
- ``h2d``        — host→device transfer of the batch and state
  (``parallel.TrainStep``'s ``device_put`` block);
- ``enqueue``    — the host's work to launch one fused ``TrainStep``
  program: step bookkeeping, the call of the jitted program, writing the
  new handles back.  The call is asynchronous — it returns before the
  device has finished — so this is host time, never compute: what the chip
  did meanwhile only a device trace says;
- ``compute``    — forward/backward/dispatch of the imperative
  ``gluon.Trainer`` path; also absorbs all *unattributed* step time (user
  code between steps; around a ``TrainStep`` that is mostly the host
  waiting for the device's results), so the phases always sum to the
  step's wall time;
- ``comms``      — gradient reduction (``trainer.allreduce``, which wraps
  the kvstore pushpull / fused psum path);
- ``optimizer``  — the weight update (imperative path; a ``TrainStep``
  fuses it into its program).

``gluon.Trainer.step`` and ``parallel.TrainStep`` drive the process-global
``STEP_CLOCK`` whenever telemetry is enabled (callers gate on the tracer
flag — this module reads no flags itself, keeping graftcheck GC05 happy; a
``TrainStep`` hands the flag to :func:`close_dispatch`).
Every finished step observes into the ``mxnet_step_phase_seconds`` labeled
histograms and a bounded rolling window (``MXNET_STEPCLOCK_WINDOW``) from
which :func:`StepClock.summary` computes per-phase medians and the rolling
**verdict**: ``input-bound`` (data_wait + h2d dominate), ``host-bound``
(enqueue: the host cannot launch programs fast enough), ``comms-bound``,
or ``compute-bound`` (compute + optimizer).  ``telemetry.report()`` renders
the table; ``tools/telemetry_report.py`` renders it per rank from exported
snapshots.

A ``TrainStep`` "step" is one jitted dispatch — with ``run(steps=K)`` that
is K fused steps, so phase times are per *dispatch*; the verdict is
unaffected (it compares shares, not absolutes).

**The dispatch record.**  Whatever the telemetry flag says, every
``TrainStep`` dispatch leaves one :class:`Dispatch` in the ring
``DISPATCHES`` (the last 256, no knob), stamped by the program on
``time.perf_counter``: its four host phases (``bookkeeping_s``, ``h2d_s``,
``enqueue_s``, ``writeback_s``), whether the device was ``fed`` (an earlier
dispatch of the same ``TrainStep`` was still running when this one had been
enqueued), whether it ``built`` a program, and, once its losses are fetched,
the wait in the fetch (``fetch_wait_s``), ``t_ready`` and the ready-to-ready
``interval_s`` since the dispatch fetched before it, with ``caller_s``: what
of that interval was neither a host phase nor the wait.  The same intervals
are ``jax.profiler.TraceAnnotation``s named ``trainstep.bookkeeping``,
``.h2d``, ``.enqueue``, ``.writeback``, ``.fetch`` (and ``.resolve`` around
``TrainStep._resolve``): in a profiler session they lie on the trace's host
plane, on the device trace's clock.  The record banks the always-on
counters ``mxnet_trainstep_host_seconds{phase}``,
``mxnet_trainstep_fetch_wait_seconds`` and ``mxnet_trainstep_unfed_total``;
a fetch whose interval passes 1.5x the ring's median for the same ``steps``
gets one line of :func:`close_fetch`, which ``TrainStep`` logs.  With
telemetry enabled the finished record is also what feeds this clock
(``h2d`` to ``h2d``, the other three phases to ``enqueue``) and the
tracer's ring buffer: one measurement, two readers.

Stdlib-only; nothing here imports jax.
"""

from __future__ import annotations

import itertools
import statistics
import threading
import time
from collections import deque

from .. import config
from . import metrics as _metrics
from . import tracer as _tracer

__all__ = ["PHASES", "StepClock", "STEP_CLOCK", "report",
           "HOST_PHASES", "Dispatch", "DISPATCHES", "open_dispatch",
           "close_dispatch", "close_fetch"]

PHASES = ("data_wait", "h2d", "enqueue", "compute", "comms", "optimizer")

# verdict label -> the phases whose medians it aggregates
VERDICT_GROUPS = {
    "input-bound": ("data_wait", "h2d"),
    "host-bound": ("enqueue",),
    "comms-bound": ("comms",),
    "compute-bound": ("compute", "optimizer"),
}

_PHASE_HIST = {
    p: _metrics.histogram(
        "mxnet_step_phase_seconds",
        "Per-step wall seconds attributed to each phase of the training "
        "step (data_wait/h2d/enqueue/compute/comms/optimizer).",
        labels={"phase": p})
    for p in PHASES
}


def _pct(sorted_vals, q):
    """Nearest-rank percentile of an ascending list."""
    if not sorted_vals:
        return 0.0
    idx = min(len(sorted_vals) - 1, max(0, int(q * len(sorted_vals))))
    return sorted_vals[idx]


class StepClock:
    """Rolling per-step phase accumulator (module docstring has the full
    story).  Thread-safe: phase notes may arrive from the consumer thread
    (Trainer), the DataLoader iterator, or a pipeline assembler."""

    def __init__(self, window=None):
        if window is None:
            window = config.get_int("MXNET_STEPCLOCK_WINDOW", 64)
        self._lock = threading.Lock()
        self._window = deque(maxlen=max(2, int(window)))
        self._pending: dict = {}   # notes landing between steps (data_wait)
        self._cur = None           # open step's phase accumulation
        self._t_begin = None
        self._last_end = None      # end of the previous step (gap origin)
        self._gap = 0.0

    # -- feeding -----------------------------------------------------------

    def begin_step(self, now=None):
        """Open a step: fold pending between-step notes in and anchor the
        gap since the previous step's end (forward/backward/user code —
        attributed to compute unless noted otherwise).  ``now``: the
        ``perf_counter`` stamp of a step that was measured elsewhere."""
        if now is None:
            now = time.perf_counter()
        with self._lock:
            self._gap = (now - self._last_end) \
                if self._last_end is not None else 0.0
            self._cur = dict(self._pending)
            self._pending.clear()
            self._t_begin = now

    def note(self, phase, seconds):
        """Attribute ``seconds`` to ``phase`` — into the open step, or the
        pending pool if none is open (a DataLoader fetch between steps)."""
        if phase not in PHASES:
            raise ValueError(f"unknown step phase {phase!r}; "
                             f"phases are {PHASES}")
        with self._lock:
            tgt = self._cur if self._cur is not None else self._pending
            tgt[phase] = tgt.get(phase, 0.0) + float(seconds)

    def end_step(self, now=None):
        """Close the open step: unattributed time goes to compute, the
        record joins the rolling window, and each phase observes into its
        ``mxnet_step_phase_seconds`` histogram."""
        if now is None:
            now = time.perf_counter()
        with self._lock:
            if self._t_begin is None:
                return          # begin_step never ran (or step abandoned)
            cur, self._cur = self._cur or {}, None
            total = (now - self._t_begin) + self._gap
            noted = sum(cur.values())
            cur["compute"] = cur.get("compute", 0.0) \
                + max(0.0, total - noted)
            rec = {p: cur.get(p, 0.0) for p in PHASES}
            # noted phases can exceed the measured wall span (a fetch
            # timed on another thread overlapping the step): total always
            # covers the phases so shares stay <= 100%
            rec["total"] = max(total, sum(rec[p] for p in PHASES))
            self._window.append(rec)
            self._last_end = now
            self._t_begin = None
            self._gap = 0.0
        for p in PHASES:
            _PHASE_HIST[p].observe(rec[p])

    # -- reading -----------------------------------------------------------

    @property
    def steps(self):
        with self._lock:
            return len(self._window)

    def summary(self):
        """{steps, phases: {name: {median, p90, mean}}, groups, verdict}
        over the rolling window; verdict 'idle' when no steps recorded."""
        with self._lock:
            recs = list(self._window)
        if not recs:
            return {"steps": 0, "phases": {}, "groups": {},
                    "verdict": "idle"}
        phases = {}
        for p in PHASES + ("total",):
            vals = sorted(r[p] for r in recs)
            phases[p] = {"median": _pct(vals, 0.5), "p90": _pct(vals, 0.9),
                         "mean": sum(vals) / len(vals)}
        groups = {label: sum(phases[p]["median"] for p in members)
                  for label, members in VERDICT_GROUPS.items()}
        verdict = max(groups, key=groups.get) \
            if any(groups.values()) else "compute-bound"
        return {"steps": len(recs), "phases": phases, "groups": groups,
                "verdict": verdict}

    def verdict(self):
        """The rolling bottleneck verdict: 'input-bound' / 'host-bound' /
        'comms-bound' / 'compute-bound' ('idle' with no recorded steps)."""
        return self.summary()["verdict"]

    def reset(self):
        with self._lock:
            self._window.clear()
            self._pending.clear()
            self._cur = None
            self._t_begin = None
            self._last_end = None
            self._gap = 0.0


STEP_CLOCK = StepClock()


# -- the dispatch record (module docstring) -----------------------------------

# The four host phases of one TrainStep dispatch, in order, and the
# StepClock phase each feeds: only the device_put block is h2d, the rest is
# the host's work to launch the program.  None of it is compute.
HOST_PHASES = {"bookkeeping": "enqueue", "h2d": "h2d",
               "enqueue": "enqueue", "writeback": "enqueue"}

_SLOW_RATIO = 1.5       # an interval over this many medians gets a line
_SLOW_MIN_INTERVALS = 8     # ... once the ring holds this many to compare
_SLOW_MIN_EXCESS_S = 0.01   # ... and is this much over: a millisecond step
#                             on a shared CPU doubles now and then, and a
#                             line names nothing anyone could act on

_HOST_SECONDS = {
    p: _metrics.gauge(
        "mxnet_trainstep_host_seconds",
        "Host seconds of TrainStep dispatches by phase (bookkeeping/h2d/"
        "enqueue/writeback); only grows.", labels={"phase": p})
    for p in HOST_PHASES
}
_FETCH_WAIT_SECONDS = _metrics.gauge(
    "mxnet_trainstep_fetch_wait_seconds",
    "Seconds the host waited inside the fetch of TrainStep dispatches' "
    "losses; only grows.")
_UNFED = _metrics.counter(
    "mxnet_trainstep_unfed_total",
    "TrainStep dispatches enqueued with no earlier dispatch of the step "
    "still running: the device had nothing queued.")


class Dispatch:
    """One ``TrainStep`` dispatch as the program stamped it
    (``perf_counter`` seconds).  ``owner`` tells the dispatches of one
    ``TrainStep`` from another's; ``fed``: an earlier dispatch of the owner
    was still not ready when this one's enqueue phase returned; ``built``:
    the dispatch built its program.  The last six fields stay ``None``
    until the losses are fetched: ``late_fetch`` says they were ready
    before the fetch began, so ``t_ready`` is the host's time and not the
    device's; ``interval_s`` is ``t_ready`` less that of the owner's
    dispatch fetched before (None for the first), ``caller_s`` what of it
    was neither a host phase of a dispatch begun inside it nor this
    fetch's wait."""

    __slots__ = ("seq", "owner", "steps", "t_begin", "bookkeeping_s",
                 "h2d_s", "enqueue_s", "writeback_s", "t_enqueued", "fed",
                 "built", "t_fetch", "fetch_wait_s", "t_ready", "late_fetch",
                 "interval_s", "caller_s")

    def __init__(self, seq, owner, steps, t_begin):
        self.seq = seq
        self.owner = owner
        self.steps = steps
        self.t_begin = t_begin
        self.t_fetch = self.fetch_wait_s = self.t_ready = None
        self.late_fetch = self.interval_s = self.caller_s = None

    def as_dict(self):
        return {k: getattr(self, k) for k in self.__slots__}


DISPATCHES = deque(maxlen=256)
_SEQ = itertools.count()
OWNERS = itertools.count()      # a TrainStep draws its ``owner`` here


def open_dispatch(owner, steps):
    """The record of a dispatch that begins now."""
    return Dispatch(next(_SEQ), owner, steps, time.perf_counter())


def close_dispatch(rec, spans, fed, built, enabled):
    """Fill ``rec`` from its four host phases (``spans``: name ->
    ``(t0, t1)``), bank the counters and put it in the ring.  ``enabled``
    is the caller's reading of the telemetry flag: the same stamps then
    feed ``STEP_CLOCK`` and the tracer's ring buffer."""
    for name, (t0, t1) in spans.items():
        setattr(rec, name + "_s", t1 - t0)
        _HOST_SECONDS[name].inc(t1 - t0)
    rec.t_enqueued = spans["enqueue"][1]
    rec.fed = fed
    rec.built = built
    if not fed:
        _UNFED.inc()
    DISPATCHES.append(rec)
    if enabled:
        STEP_CLOCK.begin_step(rec.t_begin)
        for name, (t0, t1) in spans.items():
            STEP_CLOCK.note(HOST_PHASES[name], t1 - t0)
            span_to_ring(name, t0, t1)
        STEP_CLOCK.end_step(spans["writeback"][1])


def span_to_ring(name, t0, t1):
    """A ``trainstep.<name>`` interval into the tracer's ring buffer
    (``perf_counter`` and ``perf_counter_ns`` are one clock)."""
    _tracer.get_tracer().add_event("trainstep." + name, "trainstep",
                                   int(t0 * 1e9), int(t1 * 1e9))


def close_fetch(rec, t_fetch, t_ready, late, ring=None):
    """Stamp the fetch of ``rec``'s losses and bank the wait.  Returns one
    line naming what grew where the ready-to-ready interval passes
    ``_SLOW_RATIO`` medians of the ring's intervals of the same ``steps``
    (at least ``_SLOW_MIN_INTERVALS`` of them, and by at least
    ``_SLOW_MIN_EXCESS_S``), else None."""
    # a snapshot: another thread's dispatch may append meanwhile
    ring = tuple(DISPATCHES if ring is None else ring)
    rec.t_fetch, rec.t_ready, rec.late_fetch = t_fetch, t_ready, late
    rec.fetch_wait_s = t_ready - t_fetch
    _FETCH_WAIT_SECONDS.inc(rec.fetch_wait_s)
    # the owner's dispatch fetched before this one, and the dispatches it
    # began since: the newest records, a few steps back
    last, since = None, [rec]
    for r in reversed(ring):
        if r.owner != rec.owner or r is rec:
            continue
        if r.t_ready is not None and r.seq < rec.seq:
            last = r
            break
        since.append(r)
    if last is None:
        return None
    parts = {p: sum(getattr(r, p + "_s") for r in since
                    if last.t_ready <= r.t_begin < t_ready)
             for p in HOST_PHASES}
    rec.interval_s = interval = t_ready - last.t_ready
    rec.caller_s = interval - rec.fetch_wait_s - sum(parts.values())
    # slow = over the median by the ratio = over more than half of the
    # ring's intervals of these steps, each by the ratio; one pass, no sort
    peers = over = 0
    for r in ring:
        usual = r.interval_s
        if usual is not None and r.steps == rec.steps and r is not rec:
            peers += 1
            over += (usual * _SLOW_RATIO < interval
                     and usual + _SLOW_MIN_EXCESS_S < interval)
    if peers < _SLOW_MIN_INTERVALS or 2 * over <= peers:
        return None
    parts.update(fetch_wait=rec.fetch_wait_s, caller=rec.caller_s)
    return _slow_line(rec, ring, parts)


def _slow_line(rec, ring, parts):
    """What a slow dispatch says: its interval against the median of its
    peers', and the part of it (``parts``: name -> seconds inside the
    interval) that is furthest over its own median."""
    peers = [r for r in ring if r.steps == rec.steps and r is not rec
             and r.interval_s is not None]
    usual = {p: statistics.median(getattr(r, p + "_s") for r in peers)
             for p in parts}
    grew = max(parts, key=lambda p: parts[p] - usual[p])
    median = statistics.median(r.interval_s for r in peers)
    rest = ", ".join(f"{p} {v:.4f}" for p, v in parts.items() if p != grew)
    return (f"TrainStep dispatch {rec.seq} ({rec.steps} step(s)) took "
            f"{rec.interval_s:.4f} s ready to ready, "
            f"{rec.interval_s / median:.2f}x the median {median:.4f} s of "
            f"the last {len(peers)}: {grew} grew to {parts[grew]:.4f} s "
            f"from {usual[grew]:.4f} s ({rest} s); fed={rec.fed} "
            f"built={rec.built}")


def report(clock=None, registry=None):
    """Human-readable attribution report: the per-phase table over the
    rolling window, the bottleneck verdict, and the headline run counters.
    This is what ``mx.telemetry.report()`` prints."""
    clock = clock if clock is not None else STEP_CLOCK
    registry = registry if registry is not None else _metrics.REGISTRY
    s = clock.summary()
    lines = [f"step-time attribution (last {s['steps']} step(s)):"]
    if not s["steps"]:
        lines.append("  (no steps recorded — enable telemetry "
                     "[MXNET_TELEMETRY=1] and run training steps)")
        return "\n".join(lines)
    total_med = s["phases"]["total"]["median"] or 1e-12
    lines.append(f"  {'phase':<10} {'median_ms':>10} {'p90_ms':>10} "
                 f"{'mean_ms':>10} {'share':>7}")
    for p in PHASES + ("total",):
        ph = s["phases"][p]
        share = ph["median"] / total_med
        lines.append(
            f"  {p:<10} {ph['median'] * 1e3:>10.3f} {ph['p90'] * 1e3:>10.3f}"
            f" {ph['mean'] * 1e3:>10.3f} {share:>6.0%}")
    shares = " / ".join(f"{label.split('-')[0]} {v / total_med:.0%}"
                        for label, v in s["groups"].items())
    lines.append(f"verdict: {s['verdict']} ({shares})")
    counters = []
    for name in ("mxnet_trainer_steps_total",
                 "mxnet_sharding_step_dispatches_total",
                 "mxnet_sharding_retraces_total",
                 "mxnet_op_dispatch_total",
                 "mxnet_dataloader_batches_total",
                 "mxnet_resilience_deadline_exceeded_total"):
        m = registry.get(name)
        if m is not None and m.value:
            counters.append(f"  {name} = {m.value}")
    if counters:
        lines.append("counters:")
        lines.extend(counters)
    return "\n".join(lines)
