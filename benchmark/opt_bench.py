"""Per-step optimizer host-overhead benchmark: fused buckets vs per-param.

ISSUE 5 acceptance lane: at BERT-base adam shapes (~199 dense tensors,
110M params), the flat-buffer fused optimizer (`optimizer_fusion`) must
dispatch >= 4x fewer times per step than the per-param update loop and
spend less host wall time — on the chip the same collapse converts
adam's 8.9 ms/step (~2.8x its HBM bound; builder-measured through the
earlier chip access, see ROADMAP A4) toward the ~3.2 ms
bound, which is most of what the seq-512 lane needs for MFU >= 0.45.

Dispatches are measured from the telemetry registry, not guessed:
per-param = mxnet_op_dispatch_total delta (one registry dispatch per
adam/sgd update op); fused = mxnet_optimizer_fused_buckets_total delta
(one donated jitted call per bucket).

Usage:
    python benchmark/opt_bench.py [--hidden 768] [--layers 12]
        [--vocab 30522] [--steps 10] [--warmup 2] [--optimizer adam]
        [--bucket-mb 25] [--dtype float32] [--multi-precision]

Prints one JSON line per mode plus a summary:
    {"metric": "optimizer_dispatches_per_step", "mode": "fused", ...}
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from comm_bench import bert_shapes  # noqa: E402  (same param list)


def run_mode(mode, shapes, args):
    """Time `steps` whole-model optimizer steps; returns (host_s/step,
    wall_s/step, dispatches/step) with dispatches read from telemetry."""
    os.environ["MXNET_OPTIMIZER_FUSED"] = "1" if mode == "fused" else "0"
    import mxnet_tpu as mx
    from mxnet_tpu import nd, telemetry
    from mxnet_tpu import optimizer as opt
    from mxnet_tpu import optimizer_fusion as fus
    fus.reset()

    rng = np.random.RandomState(0)
    dt = args.dtype
    if dt == "bfloat16":
        import ml_dtypes
        dt = ml_dtypes.bfloat16
    weights = [nd.array(rng.standard_normal(s).astype(dt)) for s in shapes]
    grads = [nd.array(rng.standard_normal(s).astype(dt)) for s in shapes]
    indices = list(range(len(shapes)))

    kw = {"learning_rate": 1e-3, "wd": 0.01,
          "multi_precision": args.multi_precision}
    if args.optimizer == "sgd":
        kw["momentum"] = 0.9
    optzr = opt.create(args.optimizer, **kw)
    optzr.rescale_grad = 1.0 / 32
    upd = opt.get_updater(optzr)

    def step():
        if mode == "fused":
            upd.call_fused(indices, grads, weights)
        else:
            for i in indices:
                upd(i, grads[i], weights[i])

    def counts():
        return (telemetry.counter("mxnet_op_dispatch_total").value
                + telemetry.counter(
                    "mxnet_optimizer_fused_buckets_total").value)

    for _ in range(args.warmup):
        step()
    nd.waitall()
    c0 = counts()
    host_s = 0.0
    t_wall = time.perf_counter()
    for _ in range(args.steps):
        t0 = time.perf_counter()
        step()
        host_s += time.perf_counter() - t0
    nd.waitall()
    wall_s = time.perf_counter() - t_wall
    dispatches = (counts() - c0) / args.steps
    return host_s / args.steps, wall_s / args.steps, dispatches


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--hidden", type=int, default=768)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--vocab", type=int, default=30522)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--optimizer", default="adam", choices=["adam", "sgd"])
    ap.add_argument("--bucket-mb", type=float, default=25.0)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--multi-precision", action="store_true")
    args = ap.parse_args()
    os.environ["MXNET_OPTIMIZER_BUCKET_MB"] = str(args.bucket_mb)

    from mxnet_tpu import telemetry
    telemetry.enable()

    shapes = bert_shapes(args.hidden, args.layers, args.vocab)
    n_params = sum(int(np.prod(s)) for s in shapes)
    print(json.dumps({"metric": "param_tensors", "value": len(shapes),
                      "params": n_params, "optimizer": args.optimizer,
                      "dtype": args.dtype,
                      "multi_precision": args.multi_precision}))

    results = {}
    for mode in ("perparam", "fused"):
        host, wall, disp = run_mode(mode, shapes, args)
        results[mode] = (host, wall, disp)
        print(json.dumps({
            "metric": "optimizer_update", "mode": mode,
            "host_s_per_step": round(host, 6),
            "wall_s_per_step": round(wall, 6),
            "dispatches_per_step": disp,
        }))

    (h0, w0, d0), (h1, w1, d1) = results["perparam"], results["fused"]
    summary = {
        "metric": "fused_vs_perparam",
        "dispatch_ratio": round(d0 / max(d1, 1e-9), 2),
        "host_speedup": round(h0 / max(h1, 1e-9), 2),
        "wall_speedup": round(w0 / max(w1, 1e-9), 2),
        "pass_dispatch_4x": d0 / max(d1, 1e-9) >= 4.0,
    }
    print(json.dumps(summary))
    if not summary["pass_dispatch_4x"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
