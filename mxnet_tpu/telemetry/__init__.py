"""mx.telemetry — unified runtime observability.

Per-process pieces (ISSUE 1 tentpole; reference anchors:
src/profiler/profiler.cc Chrome-trace writer + aggregate_stats.cc per-op
table):

- **spans** (`tracer`) — ``telemetry.span(name, category, **attrs)`` context
  manager recording begin/end host timestamps into a ring buffer;
  ``chrome_trace()`` exports genuine Chrome-trace JSON (``traceEvents`` with
  ``ph:"X"``, ``pid``/``tid``, ``cat``, ``args``, ``process_name``/
  ``thread_name`` metadata) for chrome://tracing / Perfetto.  A span is also
  a ``jax.profiler.TraceAnnotation`` of the same name: one name on both
  timelines, the second on the device trace's clock.
- **metrics** (`metrics`) — process-global Counter/Gauge/Histogram registry
  (optionally labeled) with Prometheus-text and JSON exporters.
- **ledger** (`ledger`) — the per-op aggregate table mx.profiler renders.

The distributed observability plane (ISSUE 10) sits on top:

- **aggregate** — cross-process collection-dir protocol
  (``MXNET_TELEMETRY_DIR``): rank-tagged snapshot export at exit, merged
  Chrome trace (pid=rank) + merged Prometheus snapshot on rank 0 /
  ``tools/telemetry_report.py``; decode-pool workers ship counters back
  on their task-ack channel.
- **stepclock** — per-step data_wait/h2d/enqueue/compute/comms/optimizer
  attribution from Trainer/TrainStep, ``mxnet_step_phase_seconds{phase=}``
  histograms, and the rolling input-/host-/comms-/compute-bound verdict
  rendered by ``telemetry.report()``.  A ``TrainStep`` phase is host time
  of one dispatch: ``h2d`` (the ``device_put`` block) and ``enqueue``
  (bookkeeping, the asynchronous call of the jitted program, writeback) —
  never compute, which only a device trace sees.  Those numbers come from
  the **dispatch record**, which a ``TrainStep`` keeps whether telemetry is
  on or not: ``stepclock.DISPATCHES``, one record a dispatch (host phases,
  was the device fed, the wait in the fetch), the same intervals as
  ``trainstep.*`` ``TraceAnnotation``s, and the ``mxnet_trainstep_*``
  counters banked from it.
- **flightrec** — the always-on crash black box: bounded postmortem dumps
  on unhandled exceptions, deadline-exceeded, chaos exits, SIGTERM, and
  SIGUSR2 (``MXNET_FLIGHTREC*`` knobs).

The analytic performance observatory (ISSUE 12) completes the stack:

- **costmodel** — the per-executable compile/cost/memory ledger over
  every jit boundary the runtime owns (XLA's own flops/bytes/HBM numbers,
  no hardware needed), analytic MFU + roofline verdicts
  (``report(cost=True)``, BENCH rows), and the fits-per-shape
  ``estimate_memory`` API (``MXNET_COSTMODEL`` knobs); and, always on,
  ``mxnet_jit_build_seconds{site=,stage=}``: what a wrapped site's
  dispatches spent in JAX's trace, lower and load stages.
- **httpd** — the live scrape plane (``MXNET_TELEMETRY_PORT``):
  ``/metrics`` Prometheus exposition, ``/statusz`` run status,
  ``/ledger.json``.

Instrumentation ships wired into the runtime chokepoints: op dispatch
(ops.registry), kvstore push/pull/allreduce, gluon.Trainer step phases,
DataLoader batch fetch, and checkpoint save/load.  The resilience layer
(mx.resilience, ISSUE 3) reports through the same registry:
``mxnet_resilience_{retries,faults_injected,deadline_exceeded,resumes,
fallbacks}_total`` and ``mxnet_resilience_retry_backoff_seconds``.  Everything is gated on
one flag: ``MXNET_TELEMETRY=1`` in the environment, ``telemetry.enable()``
at runtime, or implicitly via ``mx.profiler.start()``.  When the flag is
off, the dispatch hot path pays exactly one module-attribute check and the
non-hot paths one no-op span; importing this package imports no jax (the
first recorded span and the first wrapped jit do).
"""

from __future__ import annotations

from .. import config
from . import ledger, metrics, tracer
from . import stepclock          # noqa: E402 — needs metrics loaded
from . import costmodel          # noqa: E402 — needs metrics loaded
from . import aggregate          # noqa: E402 — needs tracer/metrics/stepclock
from . import flightrec          # noqa: E402 — needs aggregate
from . import httpd              # noqa: E402 — needs metrics/costmodel
from .ledger import record_op
from .metrics import (  # noqa: F401
    DEFAULT_BUCKETS, REGISTRY, Counter, Gauge, Histogram, MetricsRegistry,
    counter, gauge, histogram, to_json, to_prometheus,
)
from .stepclock import STEP_CLOCK, StepClock  # noqa: F401
from .tracer import (  # noqa: F401
    NULL_SPAN, Span, Tracer, chrome_trace, disable, enable, enabled,
    get_tracer, instant, span,
)

__all__ = [
    "span", "instant", "enable", "disable", "enabled", "get_tracer",
    "chrome_trace", "clear",
    "Counter", "Gauge", "Histogram", "MetricsRegistry", "REGISTRY",
    "counter", "gauge", "histogram", "to_prometheus", "to_json",
    "DEFAULT_BUCKETS",
    "record_op", "record_dispatch", "ledger", "metrics", "tracer",
    "env_enabled",
    "aggregate", "flightrec", "stepclock", "StepClock", "STEP_CLOCK",
    "report", "costmodel", "httpd",
]


def report(clock=None, registry=None, cost=False):
    """The human-readable observability report: step-time attribution +
    bottleneck verdict + headline counters (stepclock.report), and — with
    ``cost=True`` — the analytic cost-ledger table (per-site flops,
    arithmetic intensity, peak-HBM, roofline verdict)."""
    out = stepclock.report(clock=clock, registry=registry)
    if cost:
        out += "\n" + costmodel.report_text()
    return out

# -- dispatch instrumentation (fed by ops.registry.invoke) -------------------
# Handles are created once; the hot path only observes into them.

_OP_COUNT = counter(
    "mxnet_op_dispatch_total", "Imperative op dispatches through ops.registry.")
_OP_SECONDS = histogram(
    "mxnet_op_dispatch_seconds", "Host-side dispatch latency per op.")
_HOOK_SECONDS = histogram(
    "mxnet_monitor_hook_seconds", "Monitor-hook overhead per dispatch.")


def record_dispatch(name, begin_ns, end_ns, hook_ns=0):
    """One imperative dispatch: counter + latency histogram + trace event +
    ledger row.  Callers gate on ``tracer._ENABLED`` so the disabled hot
    path never reaches here."""
    dt_s = (end_ns - begin_ns) / 1e9
    _OP_COUNT.inc()
    _OP_SECONDS.observe(dt_s)
    if hook_ns:
        _HOOK_SECONDS.observe(hook_ns / 1e9)
    tracer.get_tracer().add_event(name, "dispatch", begin_ns, end_ns)
    ledger.record_op(name, dt_s)


def clear():
    """Drop buffered trace events, ledger rows (op aggregate + cost), and
    the step-clock window (metrics keep counting — use REGISTRY.reset()
    to zero them)."""
    tracer.clear()
    ledger.clear()
    stepclock.STEP_CLOCK.reset()
    costmodel.LEDGER.clear()


def payload_bytes(value):
    """Best-effort byte size of an NDArray / jax array / (nested) list —
    used by the kvstore bytes-moved counters."""
    if isinstance(value, (list, tuple)):
        return sum(payload_bytes(v) for v in value)
    data = getattr(value, "_data", value)
    n = getattr(data, "nbytes", None)
    if n is not None:
        return int(n)
    # sparse NDArrays: data + indices ride separately
    total = 0
    for part in (getattr(value, "data", None), getattr(value, "indices", None)):
        if part is not None:
            total += payload_bytes(part)
    return total


# -- env switch --------------------------------------------------------------

_ENV_ENABLED = bool(config.get_int("MXNET_TELEMETRY", 0))
if _ENV_ENABLED:
    enable()

# observability plane (ISSUE 10): the flight recorder arms at import
# (always-on black box) and, with a collection dir configured, every
# process exports its rank-tagged telemetry shard at exit.
if config.get_int("MXNET_FLIGHTREC", 1):
    flightrec.install()
if config.get("MXNET_TELEMETRY_DIR"):
    aggregate.install_atexit()
# analytic observatory (ISSUE 12): the cost ledger arms from its env knob
# and the live scrape plane serves when a port is named (off by default)
costmodel.arm_from_env()
httpd.start_from_env()


def env_enabled():
    """True when MXNET_TELEMETRY turned telemetry on at import — the
    profiler facade then never turns it off on stop()."""
    return _ENV_ENABLED
