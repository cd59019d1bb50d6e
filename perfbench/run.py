"""One run of one cell: ``python3 -m perfbench.run --workload <name> --seed <n>
--seconds <s> --trace <0|1>`` from the root of a checkout.

The harness knows no cell, configuration or metric by name.  It looks the
cell up in BENCHMARK.json, loads the configuration's file, the traffic's
file (``perfbench/traffic/<traffic>.json``) and the cell's own file
(``perfbench/workloads/<cell>.json``: the limits of its comparison and what
its compiled program must hold), hands them to the runner the traffic names
(``perfbench/runners/<runner>.py``) and asks one reader per metric
(``perfbench/metrics/<metric>.py``) for its value.  It refuses to run
without the accelerator the cell asks for: there is no fallback.
"""

import time

_START = time.perf_counter()    # set-up is counted from here

import argparse                 # noqa: E402
import contextlib               # noqa: E402
import importlib.util           # noqa: E402
import json                     # noqa: E402
import os                       # noqa: E402
import shutil                   # noqa: E402
import sys                      # noqa: E402
import tempfile                 # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "perfbench")


class Refused(Exception):
    """The run cannot be made as asked: no result is printed."""


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name, bench=None):
    bench = bench or load_json(ROOT, "BENCHMARK.json")
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise Refused(f"BENCHMARK.json has no workload {name!r}")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == entry["config"])
    cell = dict(load_json(HERE, "workloads", name + ".json"))
    cell.update(name=name, chips=entry["chips"],
                config=load_json(ROOT, cfg_entry["file"]),
                traffic=load_json(HERE, "traffic",
                                  entry["traffic"] + ".json"))
    mesh = 1
    for n in cell["traffic"]["mesh"]["shape"]:
        mesh *= n
    if mesh != cell["chips"]:
        raise Refused(f"{name}: the traffic's mesh has {mesh} devices, the "
                      f"cell asks for {cell['chips']} chips")
    return bench, cell


def check_devices(devices, chips, peaks):
    """The peaks row of the devices this cell may run on; Refused where
    JAX found no accelerator of the table, or too few chips."""
    kind = devices[0].device_kind
    row = peaks.get(kind)
    if row is None or devices[0].platform != row["platform"]:
        raise Refused(
            f"no accelerator to measure on: JAX found platform "
            f"{devices[0].platform!r}, device kind {kind!r}, which "
            f"perfbench/peaks.json does not list ({sorted(peaks)}); the "
            "benchmark never falls back to another device")
    if len(devices) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX found "
                      f"{len(devices)}")
    return row


class Clock:
    """Process start, and compilations as jax.monitoring reports them."""

    def __init__(self, start):
        import jax.monitoring as monitoring
        self.start = start
        self.compiles = 0
        self.cache_hits = self.cache_misses = 0
        monitoring.register_event_duration_secs_listener(self._duration)
        monitoring.register_event_listener(self._event)

    def _duration(self, event, _seconds, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self.compiles += 1

    def _event(self, event, **_kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1


class Tracer:
    """The profiler around the window, the window marked by a host span."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="perfbench_trace_")

    @contextlib.contextmanager
    def window(self):
        import jax
        from perfbench.trace_reduce import WINDOW_SPAN
        # device ops and TraceMe spans only: the Python tracer's events
        # (every call of every function) slow the host and say nothing here
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=options)
        try:
            with jax.profiler.TraceAnnotation(WINDOW_SPAN):
                yield
        finally:
            jax.profiler.stop_trace()

    @staticmethod
    def span(name):
        """A host span of the benchmark's own on the profiler's clock."""
        import jax
        return jax.profiler.TraceAnnotation(name)

    def load(self):
        from perfbench import trace_reduce as tr
        keep = (tr.DEVICE_PLANE_PREFIX, tr.HOST_PLANE_PREFIX)
        return tr.load_xplane(tr.find_xplane(self.dir),
                              lambda name: name.startswith(keep))

    def reduce(self):
        from perfbench import trace_reduce as tr
        return tr.reduce(self.load())

    def close(self):
        shutil.rmtree(self.dir, ignore_errors=True)


def read_metric(name, run):
    """The value of one metric from its reader, or None where the reader
    finds nothing to read in this run."""
    path = os.path.join(HERE, "metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(
        "perfbench.metrics." + name.replace(".", "_"), path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module.read(run)


def metrics_of(bench, cell, trace, run):
    out = {}
    for m in bench["per_layer" if trace else "end_to_end"]:
        if "workloads" in m and cell["name"] not in m["workloads"]:
            continue
        value = read_metric(m["name"], run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def run_cell(bench, cell, seed, seconds, tracer, devices, peak, start=None):
    """Everything of a run after the look for a chip; returns the result
    line's object.  ``tracer``: a Tracer for a traced run, else None.  The
    tests drive this on the CPU."""
    from perfbench import compare
    clock = Clock(_START if start is None else start)
    runner = importlib.import_module(
        "perfbench.runners." + cell["traffic"]["runner"])
    trace = tracer is not None
    try:
        run = runner.run(cell, seed, seconds, tracer, devices, clock)
        run.update(cell=cell, peak=peak,
                   trace=tracer.reduce() if tracer else None)
    finally:
        if tracer:
            tracer.close()
    planned = run["program"]["planned_bytes"]
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(run["peak_bytes_in_use"], planned),
              "memory_peak_source": "program_planned_bytes"
              if planned > run["peak_bytes_in_use"] else "memory_stats",
              "memory_stats_peak_bytes": run["peak_bytes_in_use"],
              "program_planned_bytes": planned}
    result = {"correct": run["correct"], "attempted": run["attempted"],
              "failed": run["failed"],
              "metrics": metrics_of(bench, cell, trace, run),
              "device": device}
    if trace:
        device.update(busy_s=run["trace"]["busy_s"],
                      window_s=run["trace"]["window_s"])
        result["breakdown"] = {"device_ops": run["trace"]["device_ops"],
                               "idle_gaps": run["trace"]["idle_gaps"]}
        modules = run["program"].pop("hlo_modules")
        result["program"] = dict(run["program"], tpu_custom_calls=sum(
            m.to_string().count("tpu_custom_call") for m in modules))
    result["workload"] = cell["name"]
    result["seed"] = seed
    result["setup_stages"] = run["setup_stages"]
    result["compile_cache"] = {"hits": clock.cache_hits,
                               "misses": clock.cache_misses}
    result["compared"] = compare.as_json(run["rows"])
    compare.report(run["rows"], run["correct"])
    return result


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        bench, cell = load_cell(args.workload)
        import jax
        import mxnet_tpu  # noqa: F401 — sets the compile cache's directory
        devices = jax.devices()
        peak = check_devices(devices, cell["chips"],
                             load_json(HERE, "peaks.json"))
        result = run_cell(bench, cell, args.seed, args.seconds,
                          Tracer() if args.trace else None, devices, peak)
    except Refused as e:
        print(f"perfbench: {e}", file=sys.stderr)
        return 2
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
