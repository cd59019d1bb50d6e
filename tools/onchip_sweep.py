#!/usr/bin/env python3
"""onchip_sweep — one budgeted pass over every r6–r12 perf lane.

The perf claims of PRs 2–14 (rounds r6–r12) were made without a chip;
each came with an "on-chip recipe" — this script IS those recipes.  It
has not been brought up on a chip (ROADMAP C4 weighs deleting it once
the benchmark exists):

    python tools/onchip_sweep.py                     # on-chip, full cost
    python tools/onchip_sweep.py --budget-s 1800     # cap total wall time
    python tools/onchip_sweep.py --dryrun            # CPU wiring proof
    python tools/onchip_sweep.py --lanes r10,r12 --json out.json

One consolidated BENCH row per lane lands on stdout (machine-parseable,
one JSON object per line), human
narration on stderr.  Lanes:

  r6   opt_bench       fused-optimizer dispatch collapse + step time
  r7   serve_bench     continuous-batching knee + flops/token           ┐ one
  r12  serve_bench     prefix-cache + speculative-decode ratios        ┘ run
  r8   data_bench      decode-pool images/sec
  r9   perfgate lane   dp2×fsdp2×tp2 mesh — measured vs analytic MFU
  r10  perfgate lane   bert headline — the analytic-MFU protocol row
  r11  autoshard       planner plan.json vs the committed golden

The measured-vs-analytic contract (r10 addendum): lanes that produce a
perfgate record assert ``|measured_mfu − analytic_mfu| / analytic_mfu``
within ``MXNET_PERFGATE_MFU_BAND`` (default 0.25) — *asserted* in real
mode, *reported* in ``--dryrun`` (single-core CPU wall time is noise,
the wiring is what the dryrun proves).  The fresh ``analytic_mfu`` is
additionally pinned to the committed ``tests/perf_baseline.json`` record
within the gate's own 2% band in BOTH modes — the sweep and the CI gate
answer to one set of numbers.

``--dryrun`` shrinks every lane to seconds, pins ``JAX_PLATFORMS=cpu``,
tolerates a nonzero benchmark exit (recorded in the row — some lanes
assert hardware-scale ratios) but requires parseable rows from each:
that is the end-to-end wiring proof the tier-1 test runs.

Exit codes: 0 all lanes ok, 1 lane failure / MFU-band violation, 2 bad
baseline.  Stays jax-free in the parent (every lane is a child process).
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import subprocess
import sys
import time
import types

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO_ROOT not in sys.path:
    sys.path.insert(0, REPO_ROOT)


def _load_perfgate():
    """tools/telemetry_report.py standalone trick — no jax in the parent."""
    if "mxnet_tpu" in sys.modules:
        return importlib.import_module("mxnet_tpu.telemetry.perfgate")
    pkg_name = "_telemetry_report_pkg"
    pkg = sys.modules.get(pkg_name)
    if pkg is None:
        pkg = types.ModuleType(pkg_name)
        pkg.__path__ = [os.path.join(REPO_ROOT, "mxnet_tpu")]
        sys.modules[pkg_name] = pkg
    return importlib.import_module(pkg_name + ".telemetry.perfgate")


# -- lane matrix -------------------------------------------------------------
# kind "bench":    run cmd, parse JSON rows, pick headline metrics
# kind "perfgate": run tools/perfgate.py --lane, check MFU bands
# kind "golden":   run cmd, parse ONE JSON doc, diff against a committed file
# share: lanes naming the same key reuse one child run (r7+r12 = one
# serve_bench pass; its sections cover both addenda)

_PY = sys.executable


def _serve_cmd(dry):
    if dry:
        return [_PY, "benchmark/serve_bench.py", "--config", "llama_tiny",
                "--vocab", "101", "--requests", "8", "--max-batch", "4",
                "--gen-tokens", "6", "--flops-max-len", "32",
                "--tp-max-seq", "64", "--block-tokens", "8",
                "--prefill-tokens", "16", "--prefill-tokens-prefix", "48",
                "--spec-k", "2"]
    return [_PY, "benchmark/serve_bench.py"]


LANES = [
    {"name": "r06_opt_fusion", "row": "r6", "kind": "bench",
     "desc": "fused-optimizer dispatch collapse (opt_bench)",
     "real": [_PY, "benchmark/opt_bench.py", "--dtype", "bfloat16",
              "--multi-precision"],
     "dry": [_PY, "benchmark/opt_bench.py", "--hidden", "64", "--layers",
             "2", "--vocab", "256", "--steps", "2", "--warmup", "1"],
     "headline": ("fused_vs_perparam", "optimizer_dispatches_per_step")},
    {"name": "r07_serve_knee", "row": "r7", "kind": "bench",
     "desc": "continuous-batching knee + flops/token (serve_bench)",
     "share": "serve",
     "headline": ("serve_flops_ratio", "serve_batching_ratio")},
    {"name": "r08_data_pipeline", "row": "r8", "kind": "bench",
     "desc": "multi-core decode pool images/sec (data_bench)",
     "real": [_PY, "benchmark/data_bench.py"],
     "dry": [_PY, "benchmark/data_bench.py", "--images", "48", "--workers",
             "2", "--trials", "2", "--batch", "16", "--size", "64",
             "--crop", "56"],
     "headline": ("data_bench_pooled_images_per_sec",
                  "data_bench_single_process_images_per_sec")},
    {"name": "r09_mesh_mfu", "row": "r9", "kind": "perfgate",
     "desc": "dp2×fsdp2×tp2 mesh lane — measured vs analytic MFU",
     "lane": "multichip_dp2fsdp2tp2"},
    {"name": "r10_analytic_mfu", "row": "r10", "kind": "perfgate",
     "desc": "bert headline lane — the analytic-MFU protocol row",
     "lane": "bert_headline"},
    {"name": "r11_fsdp_crossover", "row": "r11", "kind": "golden",
     "desc": "autoshard plan vs committed golden (planner determinism)",
     "real": [_PY, "tools/autoshard.py", "--model", "llama_small",
              "--vocab", "64", "--batch", "16", "--seq", "16",
              "--devices", "8", "--hbm-mb", "18.6", "--json"],
     "golden": "tests/autoshard_plan_golden.json"},
    {"name": "r12_spec_prefix", "row": "r12", "kind": "bench",
     "desc": "prefix-cache + spec-decode ratios (serve_bench, shared run)",
     "share": "serve",
     "headline": ("serve_prefix_ratio", "serve_spec_ratio")},
]


def _lane_env(dry, device_count=1):
    env = dict(os.environ)
    if dry:
        # the CPU wiring proof pins the virtual platform exactly like the
        # perfgate child env; real mode leaves the accelerator visible
        env["JAX_PLATFORMS"] = "cpu"
        env["XLA_FLAGS"] = \
            f"--xla_force_host_platform_device_count={device_count}"
    for k in ("MXNET_TELEMETRY_DIR", "MXNET_TELEMETRY_PORT"):
        env.pop(k, None)
    return env


def _run_child(cmd, env, timeout_s):
    t0 = time.monotonic()
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout_s, env=env, cwd=REPO_ROOT)
        rc, out, err = proc.returncode, proc.stdout, proc.stderr
    except subprocess.TimeoutExpired as e:
        rc = -1
        out = (e.stdout or b"").decode() if isinstance(e.stdout, bytes) \
            else (e.stdout or "")
        err = f"timeout after {timeout_s:.0f}s"
    wall = time.monotonic() - t0
    rows = []
    for line in out.splitlines():
        line = line.strip()
        if not line.startswith("{"):
            continue
        try:
            rows.append(json.loads(line))
        except ValueError:
            pass
    return {"rc": rc, "wall_s": round(wall, 3), "rows": rows,
            "stdout": out,
            "stderr_tail": (err or "").strip().splitlines()[-4:]}


def _pick_headline(rows, wanted):
    """The consolidated row keeps only each lane's acceptance metrics."""
    out = {}
    for w in wanted:
        for r in rows:
            if r.get("metric") == w:
                out[w] = {k: v for k, v in r.items() if k != "metric"}
                break
    return out


def _mfu_bands(rec, base_lane, band):
    """(checks, ok_analytic, ok_measured) for one perfgate record."""
    analytic = rec["metrics"]["analytic_mfu"]
    measured = rec.get("observed", {}).get("measured_mfu", 0.0)
    checks = {"analytic_mfu": analytic, "measured_mfu": measured,
              "band": band}
    ok_analytic = True
    if base_lane is not None:
        base_mfu = base_lane["metrics"]["analytic_mfu"]
        rel = abs(analytic - base_mfu) / max(abs(base_mfu), 1e-9)
        ok_analytic = rel <= 0.02    # the gate's own flops-class band
        checks["baseline_analytic_mfu"] = base_mfu
        checks["analytic_vs_baseline_rel"] = round(rel, 6)
        checks["analytic_within_gate_band"] = ok_analytic
    rel_m = abs(measured - analytic) / max(abs(analytic), 1e-9)
    ok_measured = rel_m <= band
    checks["measured_vs_analytic_rel"] = round(rel_m, 6)
    checks["measured_within_band"] = ok_measured
    return checks, ok_analytic, ok_measured


def main(argv=None):
    ap = argparse.ArgumentParser(
        description="budgeted r6–r12 perf sweep")
    ap.add_argument("--dryrun", action="store_true",
                    help="CPU wiring proof: tiny shapes, pinned platform, "
                         "benchmark rc tolerated, MFU band reported only")
    ap.add_argument("--budget-s", type=float, default=3600.0,
                    help="total wall-clock budget; lanes past it are "
                         "skipped loudly (default 3600)")
    ap.add_argument("--lanes", metavar="A,B",
                    help="restrict to these lanes (names or r-rows, "
                         "e.g. r10,r12 or r10_analytic_mfu)")
    ap.add_argument("--baseline", metavar="PATH",
                    help="perfgate baseline for the MFU pin "
                         "(default: tests/perf_baseline.json)")
    ap.add_argument("--json", metavar="PATH",
                    help="also write the full report document here")
    args = ap.parse_args(argv)

    pg = _load_perfgate()
    baseline_path = args.baseline or pg.default_baseline_path()
    base_lanes = {}
    if os.path.exists(baseline_path):
        try:
            base_lanes = pg.load_baseline(baseline_path)["lanes"]
        except pg.BaselineError as e:
            print(f"onchip_sweep: {e}", file=sys.stderr)
            return 2
    else:
        print(f"onchip_sweep: no baseline at {baseline_path} — "
              f"analytic-MFU pin skipped", file=sys.stderr)

    lanes = LANES
    if args.lanes:
        sel = {s.strip() for s in args.lanes.split(",") if s.strip()}
        lanes = [l for l in LANES if l["name"] in sel or l["row"] in sel]
        unknown = sel - {l["name"] for l in lanes} - {l["row"] for l in lanes}
        if unknown:
            raise SystemExit(
                f"unknown lane(s) {sorted(unknown)}; have "
                f"{[l['name'] for l in LANES]}")

    try:
        band = float(os.environ.get("MXNET_PERFGATE_MFU_BAND", "0.25"))
    except ValueError:
        band = 0.25

    t_start = time.monotonic()
    shared = {}
    results = []
    failed = []
    for lane in lanes:
        spent = time.monotonic() - t_start
        left = args.budget_s - spent
        if left <= 0:
            row = {"metric": f"sweep_{lane['name']}", "row": lane["row"],
                   "ok": False, "skipped": "budget exhausted",
                   "budget_s": args.budget_s, "spent_s": round(spent, 1)}
            results.append(row)
            failed.append(lane["name"])
            print(json.dumps(row, sort_keys=True))
            print(f"  [SKIP] {lane['name']} — budget exhausted "
                  f"({spent:.0f}s/{args.budget_s:.0f}s)", file=sys.stderr)
            continue
        print(f"onchip_sweep: lane {lane['name']} ({lane['desc']}) …",
              file=sys.stderr)
        row = {"metric": f"sweep_{lane['name']}", "row": lane["row"],
               "desc": lane["desc"], "mode": "dryrun" if args.dryrun
               else "onchip"}
        ok = True

        if lane["kind"] == "perfgate":
            cmd = [_PY, "tools/perfgate.py", "--lane", lane["lane"]]
            # the perfgate lanes are the analytic protocol rows: they pin
            # the virtual platform in BOTH modes (the record is the
            # hardware-free contract; on-chip MFU rides the bench lanes)
            env = _lane_env(True, pg.lane_device_count(lane["lane"]))
            res = _run_child(cmd, env, left)
            row["rc"], row["wall_s"] = res["rc"], res["wall_s"]
            if res["rc"] != 0 or not res["rows"]:
                ok = False
                row["error"] = "lane child failed"
                row["stderr_tail"] = res["stderr_tail"]
            else:
                rec = res["rows"][-1]
                checks, ok_a, ok_m = _mfu_bands(
                    rec, base_lanes.get(lane["lane"]), band)
                row["mfu"] = checks
                row["lane"] = lane["lane"]
                # analytic pin holds in BOTH modes (deterministic);
                # the measured band is hardware signal — real mode only
                ok = ok_a and (ok_m or args.dryrun)

        elif lane["kind"] == "golden":
            res = _run_child(lane["real"], _lane_env(args.dryrun), left)
            row["rc"], row["wall_s"] = res["rc"], res["wall_s"]
            golden_path = os.path.join(REPO_ROOT, lane["golden"])
            # the planner prints ONE indented JSON document (the exact
            # bytes the CI golden diff checks), not per-line rows
            plan = None
            if res["rc"] == 0:
                try:
                    plan = json.loads(res["stdout"])
                except ValueError:
                    plan = None
            if plan is None:
                ok = False
                row["error"] = "planner child failed"
                row["stderr_tail"] = res["stderr_tail"]
            else:
                with open(golden_path) as f:
                    golden = json.load(f)
                match = plan == golden
                row["golden"] = lane["golden"]
                row["plan_matches_golden"] = match
                row["mesh"] = plan.get("mesh")
                ok = match
        else:   # bench
            key = lane.get("share")
            if key is not None and key in shared:
                res = shared[key]
                row["shared_run"] = True
            else:
                cmd = lane.get("dry") if args.dryrun else lane.get("real")
                if cmd is None:
                    cmd = _serve_cmd(args.dryrun)
                res = _run_child(cmd, _lane_env(args.dryrun), left)
                if key is not None:
                    shared[key] = res
            row["rc"], row["wall_s"] = res["rc"], res["wall_s"]
            row["rows_parsed"] = len(res["rows"])
            row["headline"] = _pick_headline(res["rows"], lane["headline"])
            if not res["rows"]:
                ok = False
                row["error"] = "no parseable BENCH rows"
                row["stderr_tail"] = res["stderr_tail"]
            elif res["rc"] != 0 and not args.dryrun:
                # real mode: a failing benchmark is a failing lane; the
                # dryrun only proves wiring (tiny shapes can miss the
                # hardware-scale ratio gates) and records the rc
                ok = False
                row["error"] = f"benchmark rc={res['rc']}"
                row["stderr_tail"] = res["stderr_tail"]

        row["ok"] = ok
        if not ok:
            failed.append(lane["name"])
        results.append(row)
        print(json.dumps(row, sort_keys=True))
        state = "ok" if ok else "FAIL"
        print(f"  [{state:>4}] {lane['name']} rc={row.get('rc')} "
              f"wall={row.get('wall_s', 0):.1f}s", file=sys.stderr)

    summary = {
        "metric": "onchip_sweep_summary",
        "mode": "dryrun" if args.dryrun else "onchip",
        "lanes": len(results),
        "ok": len(results) - len(failed),
        "failed": failed,
        "mfu_band": band,
        "baseline": baseline_path if base_lanes else None,
        "budget_s": args.budget_s,
        "spent_s": round(time.monotonic() - t_start, 1),
    }
    print(json.dumps(summary, sort_keys=True))
    print(f"onchip_sweep verdict: "
          f"{'ok' if not failed else 'FAIL'} "
          f"({summary['ok']}/{summary['lanes']} lanes, "
          f"{summary['spent_s']:.0f}s/{args.budget_s:.0f}s)",
          file=sys.stderr)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"summary": summary, "lanes": results}, f, indent=1,
                      sort_keys=True)
            f.write("\n")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
