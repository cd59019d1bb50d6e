"""One traced run of a cell, kept for a look by hand at where the step's
device time goes (PERF.md section 5): per region the milliseconds a step and
the share of the busy time; the mixed fusions by the regions they hold; the
instructions no rule names that take most time; the Pallas kernels by name;
and the program's own host spans inside the window.  Writes
``regions.json``, ``describe.json`` and ``result.json``.  Not part of a
benchmark run.

    python3 -m perfbench.tools.region_report --workload <cell> --seed 1 \\
        --seconds 3 --out chiprun_out/regions_<cell>
"""

import argparse
import importlib
import json
import os
import sys
import time

from perfbench import run as harness
from perfbench import scopes
from perfbench import trace_reduce as tr

PROGRAM_SPAN_PREFIX = "trainstep."


def mixed_table(ops, instructions, computations, rules, regions, mixed):
    """Seconds in mixed fusions by (region taken, regions held): says how
    much of a region's time also does another region's work."""
    out = {}
    for name, (_n, seconds) in ops.items():
        ins = scopes.instruction_of(name)
        if ins not in mixed:
            continue
        held = {scopes.region_of_path(instructions[i]["op_name"], rules)
                for i in computations[instructions[ins]["calls"]]}
        key = f"{regions[ins]} <- {'+'.join(sorted(held - {None}))}"
        out[key] = out.get(key, 0.0) + seconds
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def report(ops, texts, builder, steps, top=15):
    rules = scopes.load_regions(builder)
    regions, mixed = scopes.regions_of(texts, rules)
    instructions, computations = scopes.parse_modules(texts)
    by_region = scopes.seconds_by_region(ops, regions)
    busy = sum(by_region.values())
    unnamed = sorted(((t, n) for n, (_c, t) in ops.items()
                      if not regions.get(scopes.instruction_of(n))),
                     reverse=True)[:top]
    pallas = {}
    for name, (count, seconds) in ops.items():
        if tr.is_pallas_call(name):
            kernel = scopes.instruction_of(name).rsplit(".", 1)[0]
            c, t = pallas.get(kernel, (0, 0.0))
            pallas[kernel] = (c + count, t + seconds)
    return {
        "steps": steps, "busy_s": busy,
        "regions": {r: {"ms_per_step": 1e3 * t / steps,
                        "busy_pct": 100 * t / busy}
                    for r, t in sorted(by_region.items(),
                                       key=lambda kv: -kv[1])},
        "mixed_busy_pct": 100 * sum(
            t for n, (_c, t) in ops.items()
            if scopes.instruction_of(n) in mixed) / busy,
        "mixed_ms_per_step": {k: 1e3 * t / steps for k, t in mixed_table(
            ops, instructions, computations, rules, regions, mixed).items()},
        "unnamed_top_ms_per_step": [
            [n, 1e3 * t / steps,
             instructions.get(scopes.instruction_of(n), {}).get("opcode")]
            for t, n in unnamed],
        "pallas_kernels": {k: {"calls_per_step": c / steps,
                               "ms_per_step": 1e3 * t / steps}
                           for k, (c, t) in sorted(pallas.items())}}


def program_spans(trace):
    """name -> [count, total seconds, count inside an enqueue span of the
    benchmark] of the program's own host spans inside the window."""
    window = tr.window_of(trace)
    outer = [e for e in tr.host_spans(trace) if e[0] == "perfbench_enqueue"]
    out = {}
    for plane in trace["planes"]:
        if not plane["name"].startswith(tr.HOST_PLANE_PREFIX):
            continue
        for line in plane["lines"]:
            for name, start, dur in tr.clip(line["events"], window):
                if not name.startswith(PROGRAM_SPAN_PREFIX):
                    continue
                inside = any(s <= start and start + dur <= s + d
                             for _n, s, d in outer)
                c, t, i = out.get(name, (0, 0.0, 0))
                out[name] = (c + 1, t + dur / 1e9, i + inside)
    return out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    import mxnet_tpu  # noqa: F401
    bench, cell = harness.load_cell(args.workload)
    devices = jax.devices()
    peak = harness.check_devices(
        devices, cell["chips"], harness.load_json(harness.HERE, "peaks.json"))
    os.makedirs(args.out, exist_ok=True)
    kept = {}

    class Keeping(harness.Tracer):
        def reduce(self):
            trace = self.load()
            kept["describe"] = tr.describe(trace, top=40)
            kept["spans"] = program_spans(trace)
            kept["reduced"] = tr.reduce(trace)
            return kept["reduced"]

    # the harness hands the program's modules to the metric readers and
    # drops them; keep their text as the runner finds them
    runner = importlib.import_module(
        "perfbench.runners." + cell["traffic"]["runner"])
    stats_of = runner.program_stats

    def keeping_stats(client):
        stats = stats_of(client)
        kept["texts"] = [m.to_string() for m in stats["hlo_modules"]]
        return stats

    runner.program_stats = keeping_stats
    try:
        result = harness.run_cell(bench, cell, args.seed, args.seconds,
                                  Keeping(), devices, peak,
                                  start=time.perf_counter())
    finally:
        runner.program_stats = stats_of
    ops = max(kept["reduced"]["ops"].values(),
              key=lambda o: sum(t for _n, t in o.values()))
    out = report(ops, kept["texts"], cell["config"]["builder"],
                 result["attempted"])
    out["program_spans"] = {k: {"count": c, "seconds": t, "inside_enqueue": i}
                            for k, (c, t, i) in sorted(kept["spans"].items())}
    for name, obj in (("regions", out), ("describe", kept["describe"]),
                      ("result", result)):
        with open(os.path.join(args.out, name + ".json"), "w") as f:
            json.dump(obj, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
