#!/usr/bin/env python3
"""telemetry_report — merge a job's telemetry collection dir and report.

Every process of a run with ``MXNET_TELEMETRY_DIR`` set leaves one
rank-tagged snapshot (``telemetry-rank*-pid*.json``) in the collection
directory — at exit, and on every flight-recorder dump.  This CLI is the
rank-0 / offline side of the protocol:

    python tools/telemetry_report.py --dir /path/to/telemetry
    python tools/telemetry_report.py --dir DIR --trace merged_trace.json \\
        --prom merged.prom
    python tools/telemetry_report.py --dir DIR --json

It prints a per-rank table (spans, steps, step-phase medians, bottleneck
verdict, headline counters), the job-wide verdict tally, and optionally
writes the merged Chrome trace (``pid`` = rank, Perfetto-labeled) and the
merged Prometheus snapshot (counters/histograms summed across ranks).

Loads ``mxnet_tpu.telemetry`` standalone (the graftcheck trick), so it
runs without jax installed.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys
import types

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load_telemetry():
    """Load mxnet_tpu.telemetry (+ its config dependency) under private
    names so mxnet_tpu's package __init__ (which imports jax) never runs."""
    if "mxnet_tpu" in sys.modules:
        return importlib.import_module("mxnet_tpu.telemetry")
    pkg_name = "_telemetry_report_pkg"
    pkg = sys.modules.get(pkg_name)
    if pkg is None:
        pkg = types.ModuleType(pkg_name)
        pkg.__path__ = [os.path.join(REPO_ROOT, "mxnet_tpu")]
        sys.modules[pkg_name] = pkg
    return importlib.import_module(pkg_name + ".telemetry")


def _fmt_ms(v):
    return f"{v * 1e3:.3f}"


def _rank_row(snap):
    sc = snap.get("stepclock") or {}
    phases = sc.get("phases") or {}
    meds = {p: (phases.get(p) or {}).get("median", 0.0)
            for p in ("data_wait", "h2d", "enqueue", "compute", "comms",
                      "optimizer", "total")}
    counters = {}
    for e in snap.get("metrics", ()):
        if e.get("kind") == "counter" and e.get("value"):
            counters[e["name"]] = e["value"]
    return {
        "rank": snap.get("rank"),
        "pid": snap.get("pid"),
        "host": snap.get("host"),
        "spans": len(snap.get("events") or ()),
        "steps": sc.get("steps", 0),
        "verdict": sc.get("verdict", "idle"),
        "phase_median_ms": {p: round(v * 1e3, 3) for p, v in meds.items()},
        "counters": counters,
    }


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="merge + report a MXNET_TELEMETRY_DIR collection")
    ap.add_argument("--dir", default=os.environ.get("MXNET_TELEMETRY_DIR"),
                    help="collection directory "
                         "(default: $MXNET_TELEMETRY_DIR)")
    ap.add_argument("--trace", metavar="PATH",
                    help="write the merged Chrome trace JSON here")
    ap.add_argument("--prom", metavar="PATH",
                    help="write the merged Prometheus snapshot here")
    ap.add_argument("--json", action="store_true",
                    help="emit the report as JSON instead of a table")
    ap.add_argument("--all-shards", action="store_true",
                    help="keep every shard (default: newest per rank)")
    ap.add_argument("--cost", action="store_true",
                    help="also render each rank's analytic cost ledger "
                         "(per-site flops / arithmetic intensity / "
                         "peak-HBM / roofline verdict)")
    args = ap.parse_args(argv)
    if not args.dir:
        ap.error("no collection dir: pass --dir or set MXNET_TELEMETRY_DIR")

    telemetry = _load_telemetry()
    agg = telemetry.aggregate
    snaps = agg.load_snapshots(args.dir,
                               latest_per_rank=not args.all_shards)
    if not snaps:
        print(f"no telemetry snapshots under {args.dir}", file=sys.stderr)
        return 1

    rows = [_rank_row(s) for s in snaps]
    if args.cost:
        cm = telemetry.costmodel
        for r, s in zip(rows, snaps):
            block = s.get("costmodel") or {}
            summ = cm.summarize_entries(block.get("entries") or (),
                                        block.get("calls") or {})
            for site, v in summ.items():
                v.update(cm.roofline(v["flops"], v["bytes_accessed"]))
            r["cost"] = summ
    if args.json:
        print(json.dumps({"ranks": rows}, indent=1))
    else:
        print(f"telemetry report — {len(rows)} rank(s) from {args.dir}")
        hdr = (f"  {'rank':>4} {'steps':>5} {'spans':>6} {'verdict':<14} "
               f"{'data_wait':>10} {'h2d':>8} {'enqueue':>9} {'compute':>9} "
               f"{'comms':>8} {'optimizer':>10}   (median ms)")
        print(hdr)
        for r in rows:
            m = r["phase_median_ms"]
            print(f"  {r['rank']:>4} {r['steps']:>5} {r['spans']:>6} "
                  f"{r['verdict']:<14} {m['data_wait']:>10.3f} "
                  f"{m['h2d']:>8.3f} {m['enqueue']:>9.3f} "
                  f"{m['compute']:>9.3f} {m['comms']:>8.3f} "
                  f"{m['optimizer']:>10.3f}")
        tally: dict = {}
        for r in rows:
            tally[r["verdict"]] = tally.get(r["verdict"], 0) + 1
        job = max(tally, key=tally.get)
        print(f"job verdict: {job} "
              f"({', '.join(f'{k}×{v}' for k, v in sorted(tally.items()))})")
        if args.cost:
            cm = telemetry.costmodel
            for r in rows:
                if not r.get("cost"):
                    continue
                print(f"cost ledger — rank {r['rank']}:")
                for line in cm.site_table_lines(r["cost"]):
                    print(line)

    if args.trace:
        with open(args.trace, "w") as f:
            json.dump(agg.merged_chrome_trace(snaps), f)
        print(f"merged Chrome trace -> {args.trace}")
    if args.prom:
        with open(args.prom, "w") as f:
            f.write(agg.merged_prometheus(snaps))
        print(f"merged Prometheus snapshot -> {args.prom}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
