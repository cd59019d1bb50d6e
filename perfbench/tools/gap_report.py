"""One short traced run of a cell, kept for a look by hand at what the host
did while the device waited: the window's longest idle gaps on the most idle
device, each named twice, by the program's own ``trainstep.*`` span that
covers most of it and by the ``perfbench_`` span the benchmark's breakdown
gives the same gap, beside the program's record of the window's dispatches
(``mxnet_tpu.telemetry.stepclock.DISPATCHES``).  Writes ``gaps.json``,
``dispatches.json`` and ``result.json``.  Not part of a benchmark run.

    python3 -m perfbench.tools.gap_report --workload <cell> --seed 1 \\
        --seconds 3 --out chiprun_out/gaps_<cell>
"""

import argparse
import json
import os
import sys
import time

from perfbench import counters_dispatch
from perfbench import run as harness
from perfbench import trace_reduce as tr

PROGRAM_SPAN_PREFIX = "trainstep."


def gaps(trace, top=5):
    """[[seconds, the program's span, the benchmark's span], ...] of the
    longest idle gaps inside the window, on the device that idles most."""
    window = tr.window_of(trace)
    by_program = tr.host_spans(trace, prefix=PROGRAM_SPAN_PREFIX)
    by_bench = tr.host_spans(trace)
    worst, rows = -1.0, []
    for events in tr.device_ops(trace).values():
        events = tr.clip(events, window)
        if not events:
            continue
        win = window or (events[0][1], max(s + d for _, s, d in events))
        busy = tr.busy_intervals(events)
        idle = 1 - sum(e - s for s, e in busy) / (win[1] - win[0])
        if idle > worst:
            # the same gaps in the same order, named from either side
            worst = idle
            rows = [[seconds, program, bench] for (program, seconds),
                    (bench, _s) in zip(
                        tr.idle_gaps(busy, win, by_program, top),
                        tr.idle_gaps(busy, win, by_bench, top))]
    return rows


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
            kept["gaps"] = gaps(trace)
            return tr.reduce(trace)

    result = harness.run_cell(bench, cell, args.seed, args.seconds,
                              Keeping(), devices, peak,
                              start=time.perf_counter())
    result.pop("breakdown", None)
    steps = result["attempted"]
    records = counters_dispatch.window(
        {"steps": steps, "cell": cell}) or []
    out = {"steps": steps,
           "gaps": [{"seconds": s, "program_span": p, "benchmark_span": b}
                    for s, p, b in kept["gaps"]],
           "metrics": {k: v["value"] for k, v in result["metrics"].items()}}
    for name, obj in (("gaps", out), ("result", result),
                      ("dispatches", [r.as_dict() for r in records])):
        with open(os.path.join(args.out, name + ".json"), "w") as f:
            json.dump(obj, f, indent=1)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
