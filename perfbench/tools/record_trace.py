"""Take one traced run of a cell and keep what a look by hand needs:
``describe.json`` (planes, lines, the names that take most time) and
``trace.json.gz`` (the device planes' op events and the benchmark's host
spans of the first ``--keep-ms`` of the window, the form the tests of
``trace_reduce`` read).  Not part of a benchmark run.

    python3 -m perfbench.tools.record_trace --workload <cell> --seed 1 \\
        --seconds 3 --out chiprun_out/trace_<cell>
"""

import argparse
import gzip
import json
import os
import sys
import time

from perfbench import run as harness
from perfbench import trace_reduce as tr


def trimmed(trace, keep_ns):
    window = tr.window_of(trace)
    lo = window[0] if window else min(
        e[1] for ev in tr.device_ops(trace).values() for e in ev)
    span = (lo, lo + keep_ns)
    planes = []
    for plane in trace["planes"]:
        device = plane["name"].startswith(tr.DEVICE_PLANE_PREFIX)
        lines = []
        for line in plane["lines"]:
            if device and line["name"] != tr.OPS_LINE:
                continue
            events = [e for e in tr.clip(line["events"], span)
                      if device or e[0].startswith(tr.SPAN_PREFIX)]
            if events:
                lines.append({"name": line["name"], "events": events})
        if lines:
            planes.append({"name": plane["name"], "lines": lines})
    # the window span itself is longer than what is kept: cut it to fit
    for plane in planes:
        for line in plane["lines"]:
            for e in line["events"]:
                if e[0] == tr.WINDOW_SPAN:
                    e[1], e[2] = span[0], keep_ns
    return {"planes": planes}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--keep-ms", type=float, default=300.0)
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
            kept["describe"] = tr.describe(trace)
            kept["trimmed"] = trimmed(trace, int(args.keep_ms * 1e6))
            return tr.reduce(trace)

    result = harness.run_cell(bench, cell, args.seed, args.seconds,
                              Keeping(), devices, peak,
                              start=time.perf_counter())
    with open(os.path.join(args.out, "describe.json"), "w") as f:
        json.dump(kept["describe"], f, indent=1)
    with gzip.open(os.path.join(args.out, "trace.json.gz"), "wt") as f:
        json.dump(kept["trimmed"], f)
    with open(os.path.join(args.out, "result.json"), "w") as f:
        json.dump(result, f)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
