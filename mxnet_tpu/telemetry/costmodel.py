"""Analytic cost/memory observatory — the hardware-free perf substrate.

XLA will *tell* us the flops, bytes and HBM footprint of every program
we compile, with no hardware attached: JAX's AOT API exposes the
compiler's own cost model
(``jitted.lower(...).cost_analysis()`` — flops + bytes accessed) and the
compiled executable's buffer assignment (``.compile().memory_analysis()``
— argument/output/temp/generated-code bytes).  This module turns those
into a first-class observability layer (ISSUE 12 tentpole):

- **compile/cost ledger** (:class:`CostLedger`, module-global ``LEDGER``)
  — every jit boundary the runtime owns (ops.registry dispatch,
  ``parallel.TrainStep``, the fused optimizer/kvstore bucket executables,
  the serving prefill/decode entries) routes through :func:`wrap_jit`.
  When the ledger is **armed** (``MXNET_COSTMODEL=1`` or :func:`arm`),
  each new executable records its measured compile seconds (via the
  ``jax.monitoring`` duration events, attributed by a thread-local site
  tag), its ``cost_analysis`` flops / bytes-accessed, and its
  ``memory_analysis`` argument/output/temp bytes → a per-device peak-HBM
  estimate.  Disarmed, the wrapper costs one module-flag read per call
  (and the per-op dispatch path is not wrapped at all).
- **build stages** (``mxnet_jit_build_seconds{site=,stage=}``, always on,
  armed or not) — of a dispatch through :func:`wrap_jit` that built
  something, the seconds JAX reports for each stage of the build:
  ``trace`` (the Python trace to a jaxpr: for ``parallel.TrainStep`` the
  package's own tape runs here), ``lower`` (jaxpr to MLIR module) and
  ``load`` (``backend_compile_duration``: on this JAX the compile-cache
  look-up, a real compile on a miss, and loading the executable).  It
  costs nothing until something is built: the wrapper compares one
  module-level counter before and after the call.
- **analytic MFU / roofline** (:func:`roofline`, :func:`lane_summary`) —
  ledger flops + a measured step wall-time give *analytic MFU* (the flops
  XLA counted, not a hand-derived 6N formula), arithmetic intensity, and
  the compute- vs memory-bound roofline verdict against the chip's peak
  flops and HBM bandwidth (``MXNET_PEAK_FLOPS`` / ``MXNET_PEAK_HBM_GBS``
  override the built-in device table).  ``telemetry.report(cost=True)``
  renders the site table.
- **fits-per-shape estimator** (:func:`estimate_memory`) — analytic
  per-device HBM for one fused training step (params + optimizer state +
  grads + batch + activations) under a declarative rule pack on a named
  mesh shape: the once hand-derived crossover table, computed.
  Validated against ``memory_analysis`` on the (2,2,2) llama lane
  (``__graft_entry__.dryrun_multichip`` + tests/test_costmodel.py); this
  is the input contract for the ROADMAP-3 auto-sharder.

The AOT analysis costs one extra trace per new executable (cheap) and —
for the memory numbers — one extra XLA compile (``MXNET_COSTMODEL_MEMORY
=0`` skips it); both happen only at executable-build time, so the
steady-state step overhead stays inside the telemetry 2% gate.

Import discipline: jax is imported lazily inside the armed paths only —
``tools/telemetry_report.py`` loads this package standalone without jax.
"""

from __future__ import annotations

import collections
import threading
import time
import warnings

from .. import config
from . import metrics as _metrics

__all__ = [
    "LEDGER", "CostLedger", "arm", "disarm", "armed", "wrap_jit",
    "wrap_jit_if_armed", "add_rearm_hook", "peak_flops",
    "peak_hbm_bytes_per_s", "roofline", "lane_summary", "estimate_memory",
    "report_text", "summarize_entries", "site_table_lines", "peak_bytes",
]

_ARMED = False
_lock = threading.Lock()
_REARM_HOOKS: list = []
_LISTENER_INSTALLED = False

# Compile detection rides jax.monitoring: every trace/lower/compile phase
# fires a duration event, so the listener bumps a global TICK and banks
# the durations.  A wrapper's steady-state armed cost is then ONE int
# compare — it re-probes its executable cache only after the tick moved
# (i.e. something, somewhere, compiled).  Duration attribution is
# best-effort under concurrent compiles from several threads (the drained
# pool is credited to the first wrapper that claims it); single-threaded
# dispatch — the normal case — attributes exactly.
_COMPILE_TICK = 0
_PENDING_COMPILE_S: list = []
_pending_lock = threading.Lock()

# CPU "peaks" describe no machine.  They exist so that what reads
# peak_flops()/roofline() in a process whose first device is the CPU gets a
# fixed ridge: the auto-sharder's analytic step-time model
# (autoshard/planner.py; tests/autoshard_plan_golden.json freezes its plans)
# and telemetry_report --cost.  No measured row carries an MFU against
# them: chip_smoke.py and the benchmark's runner refuse to run without a TPU.
_CPU_GATE_PEAK_FLOPS = 5e11
_CPU_GATE_PEAK_BYTES_PER_S = 5e10
# Per-chip peaks keyed by jax's ``device_kind``: (bf16 flop/s, HBM B/s).
# Source: Google Cloud TPU documentation, system architecture pages
# "TPU v5e" (197 TFLOP/s bf16, 819 GB/s; 394 is the int8 figure),
# "TPU v4" (275 TFLOP/s, 1228 GB/s), "TPU v5p" (459 TFLOP/s, 2765 GB/s).
# A TPU kind that is not listed is an error, never a default.
_TPU_PEAKS = {
    "TPU v5 lite": (197e12, 819e9),   # v5e
    "TPU v4": (275e12, 1228e9),
    "TPU v5": (459e12, 2765e9),       # v5p
}

# Build stages ride the same jax.monitoring events as time spans, banked
# whether armed or not.  A traced program's nested jits report their own
# trace inside the outer one's, so a stage's seconds are the union of its
# spans, never their sum.  The ring is bounded: spans nobody claims (the
# per-op dispatch path is not wrapped) fall off its end.
_BUILD_STAGE_OF = {
    "/jax/core/compile/jaxpr_trace_duration": "trace",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower",
    "/jax/core/compile/backend_compile_duration": "load",
}
_BUILD_SPANS: collections.deque = collections.deque(maxlen=4096)
_BUILD_TICK = 0     # spans banked so far; wrappers compare it around a call
_BUILD_HELP = (
    "Seconds of a wrap_jit site's dispatches spent building, by stage: "
    "trace (Python trace to jaxpr), lower (jaxpr to MLIR), load (compile "
    "cache look-up, compile on a miss, executable load).")

_M_EXECUTABLES = _metrics.counter(
    "mxnet_costmodel_executables_total",
    "Executables recorded into the cost ledger (one per (site, input "
    "signature) build while armed).")
_M_ANALYSIS_ERRORS = _metrics.counter(
    "mxnet_costmodel_analysis_errors_total",
    "Ledger AOT analyses that failed (entry records the error string).")
_M_COMPILE_SECONDS = _metrics.histogram(
    "mxnet_costmodel_compile_seconds",
    "Measured trace+lower+compile seconds per recorded executable.",
    buckets=(0.01, 0.05, 0.1, 0.25, 0.5, 1.0, 2.5, 5.0, 10.0, 30.0,
             60.0, 120.0, 300.0, 600.0))


def armed():
    """True while the ledger records (knob MXNET_COSTMODEL or arm())."""
    return _ARMED


def add_rearm_hook(fn):
    """Register a callback run on every arm()/disarm() — jit-cache owners
    (ops.registry) use it to drop executables built under the other mode
    so their next build picks the right wrapping."""
    with _lock:
        if fn not in _REARM_HOOKS:
            _REARM_HOOKS.append(fn)


def _run_rearm_hooks():
    with _lock:
        hooks = list(_REARM_HOOKS)
    for fn in hooks:
        try:
            fn()
        except Exception:  # noqa: BLE001 — a cache clear must not sink arming
            pass


def arm():
    """Start recording; returns the previous armed state."""
    global _ARMED
    prev = _ARMED
    _install_listener()
    with _pending_lock:      # stale pool from a prior armed era must not
        _PENDING_COMPILE_S.clear()   # skew the first new attribution
    _ARMED = True
    if not prev:
        _run_rearm_hooks()
    return prev


def disarm():
    global _ARMED
    prev = _ARMED
    _ARMED = False
    if prev:
        _run_rearm_hooks()
    return prev


def _install_listener():
    """Attribute jax's compile-phase events (trace / lower /
    backend-compile) to the site currently dispatching on this thread:
    their durations for the armed ledger's pool, their time spans for the
    build stages."""
    global _LISTENER_INSTALLED
    with _lock:
        if _LISTENER_INSTALLED:
            return
        _LISTENER_INSTALLED = True
    import jax.monitoring as jm
    jm.register_event_time_span_listener(_on_time_span)


def _on_time_span(name, start, end, **kwargs):  # noqa: ARG001
    global _BUILD_TICK, _COMPILE_TICK
    stage = _BUILD_STAGE_OF.get(name)
    if stage is None or getattr(_ANALYSIS_TLS, "active", False):
        return   # the ledger's own AOT compiles are no dispatch's build
    #          and must not bank/tick
    with _pending_lock:
        _BUILD_SPANS.append((threading.get_ident(), stage, start, end))
        _BUILD_TICK += 1
        if _ARMED:   # disarmed-era compiles must not reach the armed pool
            _PENDING_COMPILE_S.append(float(end - start))
            _COMPILE_TICK += 1


def _bank_build(site, n_spans):
    """Credit ``site`` with what this thread built during the dispatch
    that just returned: the last ``n_spans`` banked spans, by stage."""
    me = threading.get_ident()
    with _pending_lock:
        spans = list(_BUILD_SPANS)[-n_spans:]
    by_stage: dict = {}
    for tid, stage, start, end in spans:
        if tid == me:
            by_stage.setdefault(stage, []).append((start, end))
    for stage, intervals in by_stage.items():
        total, reached = 0.0, float("-inf")
        for start, end in sorted(intervals):
            if end > reached:
                total += end - max(start, reached)
                reached = end
        # a gauge by the registry's naming rule (GC09: only ``_total``
        # names a counter); it only ever grows
        _metrics.gauge("mxnet_jit_build_seconds", _BUILD_HELP,
                       labels={"site": site, "stage": stage}).inc(total)


_ANALYSIS_TLS = threading.local()


def _drain_compile_seconds():
    with _pending_lock:
        total = sum(_PENDING_COMPILE_S)
        _PENDING_COMPILE_S.clear()
    return total


# -- abstraction: call args -> lowerable avals -------------------------------

def _abstract_leaf(x, keep_sharding):
    import jax
    shape = getattr(x, "shape", None)
    dtype = getattr(x, "dtype", None)
    if shape is None or dtype is None:
        return x                       # static / scalar python value
    if keep_sharding:
        try:
            sh = x.sharding
            return jax.ShapeDtypeStruct(tuple(shape), dtype, sharding=sh)
        except Exception:  # noqa: BLE001 — deleted/np arrays, odd leaves
            pass
    return jax.ShapeDtypeStruct(tuple(shape), dtype)


def _abstract_tree(x, keep_sharding):
    """Args → ShapeDtypeStructs, recursing ONLY through plain containers.
    Namedtuples/dataclass configs pass through untouched — they are the
    static_argnums side of the serving jits and must stay concrete."""
    if type(x) in (tuple, list):
        return type(x)(_abstract_tree(v, keep_sharding) for v in x)
    if type(x) is dict:
        return {k: _abstract_tree(v, keep_sharding) for k, v in x.items()}
    return _abstract_leaf(x, keep_sharding)


def _cost_dict(lowered):
    c = lowered.cost_analysis()
    if isinstance(c, (list, tuple)):    # some backends: one dict per comp
        merged: dict = {}
        for d in c:
            for k, v in (d or {}).items():
                merged[k] = merged.get(k, 0.0) + v
        c = merged
    return c or {}


def peak_bytes(ma):
    """Per-device peak of one executable from its ``memory_analysis()``:
    donated outputs alias their argument buffers, so the peak is what
    must coexist, not the naive sum."""
    alias_b = int(getattr(ma, "alias_size_in_bytes", 0) or 0)
    return (int(ma.argument_size_in_bytes) + int(ma.temp_size_in_bytes)
            + int(ma.generated_code_size_in_bytes)
            + max(0, int(ma.output_size_in_bytes) - alias_b))


# -- the ledger --------------------------------------------------------------

class CostLedger:
    """Thread-safe per-executable cost/memory records + per-site tallies.

    Call counting stays OFF the armed hot path: each wrapper bumps its
    own lock-free ``_calls`` int (a dropped increment under a thread race
    costs one count, never a crash) and the ledger sums them on read."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: list = []
        self._wrappers: list = []       # weakrefs to _InstrumentedJit's

    # -- feeding (wrappers call these while armed) --------------------------

    def _register(self, wrapper):
        import weakref
        with self._lock:
            self._wrappers.append(weakref.ref(wrapper))
            if len(self._wrappers) % 512 == 0:   # bound growth
                self._wrappers[:] = [r for r in self._wrappers
                                     if r() is not None]

    def _call_counts(self):
        """site -> armed dispatches through currently-live wrappers (a
        rebuilt executable starts a fresh count, like its compile cache)."""
        with self._lock:
            refs = list(self._wrappers)
        out: dict = {}
        for r in refs:
            w = r()
            if w is not None and w._calls:
                out[w.site] = out.get(w.site, 0) + w._calls
        return out

    def analyze(self, site, jf, args, kwargs, compile_s=0.0):
        """AOT-analyze the executable ``jf`` just built for ``args`` and
        append the record.  Never raises: an analysis failure records an
        ``error`` entry (counted) and execution continues untouched."""
        t0 = time.perf_counter()
        entry = {"site": site, "compile_s": float(compile_s),
                 "time": time.time()}
        _ANALYSIS_TLS.active = True
        try:
            with warnings.catch_warnings():
                # lowering with donated-but-unused avals warns; the
                # analysis pass must stay silent
                warnings.simplefilter("ignore")
                entry.update(self._analyze_once(jf, args, kwargs))
        except Exception as e:  # noqa: BLE001 — ledger must never kill a step
            entry["error"] = f"{type(e).__name__}: {e}"[:300]
            _M_ANALYSIS_ERRORS.inc()
        finally:
            _ANALYSIS_TLS.active = False
        entry["analysis_s"] = round(time.perf_counter() - t0, 4)
        with self._lock:
            entry["index"] = sum(1 for e in self._entries
                                 if e["site"] == site)
            self._entries.append(entry)
        _M_EXECUTABLES.inc()
        if compile_s:
            _M_COMPILE_SECONDS.observe(compile_s)
        return entry

    def _analyze_once(self, jf, args, kwargs):
        try:
            a = _abstract_tree(tuple(args), True)
            k = {n: _abstract_tree(v, True) for n, v in kwargs.items()}
            lowered = jf.lower(*a, **k)
        except Exception:  # noqa: BLE001 — sharding-annotated avals can
            # clash with explicit in_shardings; retry shardings-free
            a = _abstract_tree(tuple(args), False)
            k = {n: _abstract_tree(v, False) for n, v in kwargs.items()}
            lowered = jf.lower(*a, **k)
        cost = _cost_dict(lowered)
        out = {
            "flops": float(cost.get("flops", 0.0) or 0.0),
            "bytes_accessed": float(cost.get("bytes accessed", 0.0) or 0.0),
        }
        if config.get_int("MXNET_COSTMODEL_MEMORY", 1):
            ma = lowered.compile().memory_analysis()
            arg_b = int(ma.argument_size_in_bytes)
            out_b = int(ma.output_size_in_bytes)
            tmp_b = int(ma.temp_size_in_bytes)
            code_b = int(ma.generated_code_size_in_bytes)
            alias_b = int(getattr(ma, "alias_size_in_bytes", 0) or 0)
            out.update(
                arg_bytes=arg_b, out_bytes=out_b, temp_bytes=tmp_b,
                code_bytes=code_b, alias_bytes=alias_b,
                peak_bytes=peak_bytes(ma))
        return out

    # -- reading ------------------------------------------------------------

    def entries(self, site=None):
        with self._lock:
            ents = list(self._entries)
        if site is None:
            return ents
        return [e for e in ents if e["site"] == site]

    def calls(self, site):
        return self._call_counts().get(site, 0)

    def site_summary(self):
        """{site: {executables, calls, compile_s, flops, bytes_accessed,
        peak_bytes, errors}} — flops/bytes/peak from each site's largest
        recorded executable (the steady-state program; warmup shapes and
        probe dispatches are smaller)."""
        with self._lock:
            ents = list(self._entries)
        return summarize_entries(ents, self._call_counts())

    def snapshot(self):
        """JSON-serializable ledger state — rides the telemetry snapshot
        (aggregate.snapshot) and the /ledger.json endpoint."""
        with self._lock:
            ents = [dict(e) for e in self._entries]
        return {"entries": ents, "calls": self._call_counts()}

    def clear(self):
        with self._lock:
            self._entries.clear()
            live = []
            for r in self._wrappers:
                w = r()
                if w is not None:
                    w._calls = 0
                    live.append(r)
            self._wrappers[:] = live


def summarize_entries(entries, calls=None):
    """Per-site roll-up of raw ledger entry dicts — shared by the live
    :meth:`CostLedger.site_summary` and the offline report CLI, which
    reads the ``costmodel`` block of exported telemetry shards."""
    calls = calls or {}
    out: dict = {}
    for e in entries:
        s = out.setdefault(e["site"], {
            "executables": 0, "calls": calls.get(e["site"], 0),
            "compile_s": 0.0, "flops": 0.0, "bytes_accessed": 0.0,
            "peak_bytes": 0, "errors": 0})
        s["executables"] += 1
        s["compile_s"] += e.get("compile_s", 0.0)
        if e.get("error"):
            s["errors"] += 1
            continue
        if (e.get("flops") or 0.0) >= s["flops"]:
            s["flops"] = e.get("flops") or 0.0
            s["bytes_accessed"] = e.get("bytes_accessed") or 0.0
        s["peak_bytes"] = max(s["peak_bytes"], e.get("peak_bytes", 0) or 0)
    return out


LEDGER = CostLedger()


# -- the jit-boundary wrapper ------------------------------------------------

class _InstrumentedJit:
    """Transparent wrapper over one jitted callable: armed, it tags the
    dispatch with its site (compile-duration attribution) and AOT-analyzes
    every NEW executable the underlying cache builds; disarmed, one flag
    read.  The armed steady-state cost is lock-free: a local call-count
    bump, one thread-local set/restore pair, and one C++ cache-size probe
    — analysis work happens only when the cache GREW (a compile, which
    already cost seconds).  Armed or not, a dispatch during which this
    thread built something banks the build's stages under the site
    (``mxnet_jit_build_seconds``): one counter compare per call."""

    __slots__ = ("_jf", "site", "_nexec", "_calls", "_tick", "__weakref__")

    def __init__(self, jf, site):
        self._jf = jf
        self.site = site
        self._nexec = 0
        self._calls = 0
        self._tick = -1     # forces a first-armed-call cache probe, so
        #                     arming AFTER an executable was built still
        #                     records it lazily on its next dispatch
        LEDGER._register(self)
        _install_listener()

    def __getattr__(self, name):        # .lower / ._cache_size passthrough
        return getattr(self._jf, name)

    def __call__(self, *args, **kwargs):
        built = _BUILD_TICK
        out = self._jf(*args, **kwargs)
        if built != _BUILD_TICK:            # something was built meanwhile
            _bank_build(self.site, _BUILD_TICK - built)
        if not _ARMED:
            return out
        self._calls += 1
        if self._tick != _COMPILE_TICK:     # something compiled: was it us?
            self._probe(args, kwargs)
        return out

    def _cache_size(self):
        try:
            return self._jf._cache_size()
        except Exception:  # noqa: BLE001 — private API; fall back below
            return None

    def _probe(self, args, kwargs):
        self._tick = _COMPILE_TICK
        n = self._cache_size()
        if n is None:
            # no cache introspection (the private pjit API moved under a
            # jax upgrade): analyze this wrapper at most ONCE — assuming
            # every foreign compile was ours would re-run the AOT
            # analysis (an extra XLA compile each) on every tick move
            if self._nexec:
                return
            n = 1
        if n != self._nexec:
            self._nexec = n
            # drain ONLY when our cache grew — another site's compile
            # leaves the pool for the wrapper that actually owns it
            LEDGER.analyze(self.site, self._jf, args, kwargs,
                           compile_s=_drain_compile_seconds())


def wrap_jit(jf, site):
    """Instrument a jitted callable under a site label.  Use at every
    boundary whose dispatch rate is per-step or slower (TrainStep, fused
    optimizer/kvstore buckets, serving entries): the disarmed cost is one
    flag read, and arming at runtime instruments executables lazily (the
    next dispatch sees the cache already populated and analyzes it)."""
    return _InstrumentedJit(jf, site)


def wrap_jit_if_armed(jf, site):
    """Instrument only when already armed — for the per-op dispatch path,
    which must stay wrapper-free when the ledger is off.  Owners register
    an :func:`add_rearm_hook` cache clear so a runtime arm() rebuilds
    their callables through this with the wrapper on."""
    if _ARMED:
        return _InstrumentedJit(jf, site)
    return jf


# -- analytic MFU + roofline -------------------------------------------------

def peak_flops(dtype="bfloat16"):
    """Per-chip peak flops for MFU accounting.  MXNET_PEAK_FLOPS wins;
    else the device table (bf16 peaks; /4 for float32).  The CPU gets a
    nominal figure that describes no machine; an unlisted TPU kind raises."""
    v = config.get_float("MXNET_PEAK_FLOPS", 0.0)
    if v > 0:
        return v
    peaks = _device_peaks()
    if peaks is None:
        return _CPU_GATE_PEAK_FLOPS
    bf16 = peaks[0]
    return bf16 if str(dtype) in ("bfloat16", "bf16") else bf16 / 4


def peak_hbm_bytes_per_s():
    """Per-chip HBM bandwidth (B/s) for the roofline ridge.
    MXNET_PEAK_HBM_GBS (in GB/s) wins; else the device table."""
    v = config.get_float("MXNET_PEAK_HBM_GBS", 0.0)
    if v > 0:
        return v * 1e9
    peaks = _device_peaks()
    return _CPU_GATE_PEAK_BYTES_PER_S if peaks is None else peaks[1]


def _device_peaks():
    """The ``_TPU_PEAKS`` row of the process's first device, or None on
    the CPU platform.  An unreachable backend propagates its error, and a
    device this table does not know raises: a wrong peak makes every
    utilization computed from it wrong."""
    import jax
    d = jax.devices()[0]
    if d.platform == "cpu":
        return None
    try:
        return _TPU_PEAKS[d.device_kind]
    except KeyError:
        raise ValueError(
            f"no peak flops/bandwidth recorded for device kind "
            f"{d.device_kind!r} (platform {d.platform!r}); add it to "
            f"costmodel._TPU_PEAKS with its source, or set "
            f"MXNET_PEAK_FLOPS / MXNET_PEAK_HBM_GBS") from None


def roofline(flops, bytes_accessed, seconds=None, dtype="bfloat16"):
    """The roofline read on one program: arithmetic intensity vs the
    machine ridge, the attainable-MFU bound it implies, and (given a
    measured wall time) the analytic MFU actually achieved."""
    pf = peak_flops(dtype)
    pb = peak_hbm_bytes_per_s()
    ai = float(flops) / max(float(bytes_accessed), 1.0)
    ridge = pf / pb
    out = {
        "flops": float(flops),
        "bytes_accessed": float(bytes_accessed),
        "arithmetic_intensity": round(ai, 3),
        "ridge_flops_per_byte": round(ridge, 3),
        "verdict": "compute-bound" if ai >= ridge else "memory-bound",
        # the ceiling the roofline itself allows at this intensity: below
        # the ridge, HBM bandwidth (not the MXU) bounds achievable MFU
        "roofline_mfu_bound": round(min(1.0, ai / ridge), 4),
        "peak_flops": pf,
        "peak_hbm_bytes_per_s": pb,
    }
    if seconds:
        out["analytic_mfu"] = round(float(flops) / (float(seconds) * pf), 4)
        out["flops_per_s"] = float(flops) / float(seconds)
    return out


def lane_summary(site="parallel.TrainStep", step_seconds=None,
                 dtype="bfloat16"):
    """The BENCH-row cost block for one lane: the site's largest recorded
    executable (its steady-state program) rooflined against the chip
    peaks, with the per-device peak-HBM estimate and compile seconds
    alongside.  The program's cost IS the per-step cost even for
    lax.scan-fused lanes — XLA's HLO cost analysis counts a while/scan
    body ONCE regardless of trip count (verified: identical flops at
    scan_steps 2 and 4), so ``step_seconds`` should be the measured
    per-STEP wall time, not per-dispatch."""
    ents = [e for e in LEDGER.entries(site) if not e.get("error")]
    if not ents:
        return {"error": f"no cost-ledger entries for site {site!r} "
                         "(costmodel not armed?)"}
    e = max(ents, key=lambda x: x.get("flops") or 0.0)
    flops = e.get("flops") or 0.0
    byts = e.get("bytes_accessed") or 0.0
    out = roofline(flops, byts, seconds=step_seconds, dtype=dtype)
    out["peak_hbm_bytes"] = e.get("peak_bytes", 0)
    out["compile_s"] = round(sum(x.get("compile_s", 0.0) for x in ents), 3)
    out["executables"] = len(ents)
    return out


def site_table_lines(summary):
    """Formatted per-site table rows from a :func:`summarize_entries`
    dict — the ONE renderer behind ``report_text`` (live) and
    ``tools/telemetry_report.py --cost`` (offline shards)."""
    lines = [f"  {'site':<28} {'exec':>5} {'calls':>7} "
             f"{'compile_s':>10} {'gflops':>10} {'AI':>7} "
             f"{'peak_hbm_mb':>12} {'verdict':<14}"]
    for site in sorted(summary):
        s = summary[site]
        rl = roofline(s["flops"], s["bytes_accessed"])
        lines.append(
            f"  {site:<28} {s['executables']:>5} {s['calls']:>7} "
            f"{s['compile_s']:>10.3f} {s['flops'] / 1e9:>10.3f} "
            f"{rl['arithmetic_intensity']:>7.1f} "
            f"{s['peak_bytes'] / 1e6:>12.2f} {rl['verdict']:<14}")
        if s["errors"]:
            lines.append(f"    ({s['errors']} analysis error(s) — see "
                         "LEDGER.entries())")
    return lines


def report_text():
    """Human-readable per-site ledger table (telemetry.report(cost=True))."""
    summ = LEDGER.site_summary()
    lines = [f"cost ledger ({len(summ)} site(s), "
             f"{sum(s['executables'] for s in summ.values())} "
             f"executable(s)):"]
    if not summ:
        lines.append("  (empty — arm with MXNET_COSTMODEL=1 or "
                     "telemetry.costmodel.arm())")
        return "\n".join(lines)
    lines.extend(site_table_lines(summ))
    return "\n".join(lines)


# -- fits-per-shape: analytic per-device HBM ---------------------------------

def _mesh_axis_sizes(mesh_shape):
    """{'dp': 2, 'tp': 2, ...} from a dict, a DeviceMesh, or a
    (shape, axis_names) pair."""
    if hasattr(mesh_shape, "axis_names"):      # DeviceMesh / jax Mesh
        names = tuple(mesh_shape.axis_names)
        try:
            sizes = tuple(mesh_shape.shape[n] for n in names)  # jax Mesh
        except TypeError:
            sizes = tuple(mesh_shape.shape)
        return dict(zip(names, sizes))
    if isinstance(mesh_shape, dict):
        return {str(k): int(v) for k, v in mesh_shape.items()}
    shape, names = mesh_shape
    return dict(zip(names, (int(s) for s in shape)))


def _sharded_numel(shape, spec, axes):
    """Element count of one param's per-device shard under ``spec`` —
    resolve_spec's exact degradation semantics (missing axes drop out,
    indivisible dims stay whole)."""
    n = 1
    spec = tuple(spec or ())
    for d, dim in enumerate(shape):
        div = 1
        if d < len(spec):
            entry = spec[d]
            entry = entry if isinstance(entry, (tuple, list)) \
                else (entry,) if entry is not None else ()
            for a in entry:
                div *= axes.get(a, 1)
        n *= dim // div if (div > 1 and dim % div == 0) else dim
    return n


def _param_table(model_cfg):
    """{name: (shape, itemsize)} from a Block, ParameterDict, or dict of
    shapes/arrays."""
    import numpy as _np
    if hasattr(model_cfg, "collect_params"):
        model_cfg = model_cfg.collect_params()
    out = {}
    for name, leaf in dict(model_cfg.items()).items():
        shape = tuple(leaf) if isinstance(leaf, (tuple, list)) \
            else tuple(leaf.shape)
        dt = getattr(leaf, "dtype", None)
        out[name] = (shape, _np.dtype(dt).itemsize if dt is not None else 4)
    return out


_EMBED_PAT = ("tok_", "word_", "embed", "position_")


def _drop_axes(spec, drop):
    """``spec`` with every axis in ``drop`` removed (per-dim entries keep
    their remaining axes)."""
    out = []
    for entry in tuple(spec or ()):
        entry = entry if isinstance(entry, (tuple, list)) \
            else (entry,) if entry is not None else ()
        kept = tuple(a for a in entry if a not in drop)
        out.append(kept if len(kept) > 1 else (kept[0] if kept else None))
    return tuple(out)


def estimate_memory(model_cfg, mesh_shape, rule_pack, batch, seq=None,
                    optimizer="adam", multi_precision=False,
                    data_axes=("dp", "sp"), vocab=None,
                    n_micro=1, remat=False, fsdp_axes=("fsdp",)):
    """Analytic per-device HBM (bytes) for ONE fused training step.

    Parameters
    ----------
    model_cfg : a gluon Block (post-init), ParameterDict, or
        ``{name: shape|array}`` dict — the named param tree the rule pack
        matches against.
    mesh_shape : ``{'dp': 2, 'tp': 2, 'sp': 2}``, a DeviceMesh, or a
        ``(shape, axis_names)`` pair.
    rule_pack : pack name (``'llama'``/``'llama_fsdp'``/``'bert'``/...),
        an ordered ``(regex, spec)`` rule list, or None (fully
        replicated).
    batch : GLOBAL batch size (samples).
    seq : tokens per sample (token models; None => 1, feature models).
    optimizer : 'adam' (m+v state) or 'sgd' (momentum assumed on).
    multi_precision : half-precision weights keep fp32 masters.
    data_axes : mesh axes the token batch shards over (data_spec) —
        include the fsdp axis for ZeRO-3 layouts (the batch rides it).
    vocab : LM-head width for the logits term; inferred from the widest
        embedding-named param when None.
    n_micro : gradient-accumulation microbatches per step (TrainStep
        ``n_micro``): live activations/logits divide by it, but a full
        gradient ACCUMULATOR joins the working set (and under fsdp the
        per-microbatch gradients live gathered inside the scan before
        their reduce-scatter — both measured on the llama lane).
    remat : TrainStep ``remat`` — saved activations halve (checkpointed
        segment stores inputs; backward recomputes with roughly half the
        residual set live).  XLA:CPU's compiled peak barely moves under
        whole-net remat (its scheduler already overlaps fwd/bwd), so
        remat'd estimates are NOT cross-checked against memory_analysis;
        the planner treats remat as the last lever (not re-measured on
        a chip).
    fsdp_axes : axes with gather-on-use semantics (params sharded along
        them are all-gathered right before each matmul).

    Returns a breakdown dict whose ``total_bytes`` is the estimated
    steady-state peak for a donated step: live arguments (params +
    optimizer state + batch) plus the backward working set (gradients +
    saved activations + the logits the loss keeps + the fsdp gather
    working set).  Validated against ``memory_analysis`` on the dryrun
    llama lanes: 2.6% off on (2,2,2) dp×tp×sp, ~1% on dp×fsdp
    (gather term = half the full-along-fsdp weight bytes, measured),
    ~15% conservative on dp-only — the input contract for the
    auto-sharder (ROADMAP 3).
    """
    axes = _mesh_axis_sizes(mesh_shape)
    table = _param_table(model_cfg)
    if rule_pack is None:
        specs = {name: () for name in table}
    else:
        from .. import sharding as _sh
        rules = _sh.rule_pack(rule_pack) if isinstance(rule_pack, str) \
            else rule_pack
        specs = _sh.match_partition_rules(
            rules, {n: shape for n, (shape, _i) in table.items()})

    if optimizer == "adam":
        n_state = 2
    elif optimizer in ("sgd", "sgd_mom"):
        n_state = 1
    else:
        raise ValueError(f"estimate_memory: unknown optimizer "
                         f"{optimizer!r} (adam|sgd)")
    n_micro = max(1, int(n_micro))

    tokens = int(batch) * int(seq or 1)
    data_div = 1
    for a in data_axes:
        data_div *= axes.get(a, 1)
    tokens_dev = max(1, tokens // data_div)
    # only one microbatch's activations are live at a time
    tokens_act = max(1, tokens_dev // n_micro)

    params_b = state_b = 0
    act_elems = 0.0
    gathered_b = 0          # full-along-fsdp bytes of gather-on-use params
    inferred_vocab = 0
    seen_inputs = set()
    fsdp_drop = frozenset(fsdp_axes)
    for name, (shape, itemsize) in table.items():
        spec = specs.get(name, ())
        numel = _sharded_numel(shape, spec, axes)
        params_b += numel * itemsize
        state_b += numel * itemsize * n_state
        if multi_precision and itemsize < 4:
            state_b += numel * 4
        nofsdp_spec = _drop_axes(spec, fsdp_drop)
        gathered = _sharded_numel(shape, nofsdp_spec, axes)
        if gathered != numel:
            # actually fsdp-sharded (divisible, axis present): the
            # all-gather before use materializes the full-along-fsdp
            # weight (still divided by any tp axes it carries)
            gathered_b += gathered * itemsize
        is_embed = any(p in name for p in _EMBED_PAT)
        if is_embed and len(shape) == 2:
            inferred_vocab = max(inferred_vocab, shape[0])
        if len(shape) == 2 and not is_embed:
            # every matmul's backward saves its input activation
            # (tokens × in_features, sharded when the weight is
            # row-parallel) and hands a same-shaped output cotangent
            # through (tokens × out_features, sharded when
            # column-parallel): count the saved input plus the layer
            # output that the residual stream keeps live.  Matmuls in
            # one layer reading the SAME activation (q/k/v, gate/up)
            # save it ONCE — dedup by (layer prefix, sharded width).
            # Activation widths use the NON-fsdp sharding: the matmul
            # runs on the gathered weight, so activations shard only
            # over tp-style axes.
            out_f = _sharded_numel((shape[0],), nofsdp_spec[:1], axes)
            in_f = _sharded_numel((shape[1],), nofsdp_spec[1:2], axes) \
                if len(nofsdp_spec) > 1 else shape[1]
            layer_key = name.rsplit("_", 2)[0]
            if (layer_key, in_f) not in seen_inputs:
                seen_inputs.add((layer_key, in_f))
                act_elems += tokens_act * in_f
            act_elems += tokens_act * out_f

    # logits head: softmax_cross_entropy saves the logits it was given and
    # a per-row logsumexp; its backward recomputes the softmax from them
    v = int(vocab) if vocab else inferred_vocab
    logits_b = tokens_act * v * 4 if v else 0
    # gradients live as temps through backward + the fused update; a
    # microbatched step additionally carries the accumulator, and under
    # fsdp the in-scan per-microbatch gradients are FULL along fsdp
    # until their reduce-scatter (measured: llama dp×fsdp micro lane)
    grads_b = params_b
    if n_micro > 1:
        grads_b += gathered_b if gathered_b else params_b
    acts_b = int(act_elems) * 4     # residuals saved in compute precision
    if remat:
        acts_b //= 2
    # gather-on-use working set: roughly half the gathered weight bytes
    # live at the peak while the scheduler can overlap gathers with
    # frees (measured 195.4KB vs 197.6KB predicted on the llama
    # dp2×fsdp4 lane) — but once the live ACTIVATION set outgrows that
    # half, XLA holds the full gathered set (measured crossover on the
    # batch-32 dp4×fsdp2 lane: half-model 14% under, full-model 3%
    # over).  Inside a microbatch scan gathers can't overlap frees
    # across the scan boundary at all, so the full set always counts
    # there (fsdp micro2 lane: within 1.5% with this, 17% under
    # without).
    if n_micro > 1:
        gather_b = gathered_b
    else:
        gather_b = min(gathered_b, max(gathered_b // 2, acts_b))
    batch_b = 2 * tokens_dev * 4    # data + label, int32 tokens
    total = (params_b + state_b + grads_b + batch_b + acts_b + logits_b
             + gather_b)
    return {
        "params_bytes": int(params_b),
        "opt_state_bytes": int(state_b),
        "grads_bytes": int(grads_b),
        "batch_bytes": int(batch_b),
        "activation_bytes": int(acts_b),
        "logits_bytes": int(logits_b),
        "fsdp_gather_bytes": int(gather_b),
        # the UN-clamped full-along-fsdp weight bytes: what one step's
        # all-gathers actually move per microbatch (the residency-
        # clamped fsdp_gather_bytes above is a PEAK-MEMORY quantity and
        # must not be used for communication accounting)
        "fsdp_gathered_bytes": int(gathered_b),
        "total_bytes": int(total),
        "tokens_per_device": tokens_dev,
        "n_micro": n_micro,
        "remat": bool(remat),
        "mesh": dict(axes),
    }


# -- env arming (telemetry.__init__ calls this at import) --------------------

def arm_from_env():
    if config.get_int("MXNET_COSTMODEL", 0):
        arm()
