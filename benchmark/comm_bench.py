"""Per-step allreduce host-overhead benchmark: fused buckets vs per-key.

ISSUE 2 acceptance lane: at a BERT-base-sized parameter list (~200 dense
tensors), `pushpull_list` with gradient fusion (MXNET_KVSTORE_BUCKET_MB
buckets, kvstore/fusion.py) must issue >= 5x fewer kvstore dispatches per
step than the per-key push+pull loop, and spend less host wall time — the
per-key path is pure host-bound dispatch overhead that a device-time
decomposition cannot see.

Dispatches are measured from the telemetry registry, not guessed:
per-key = mxnet_kvstore_push_seconds.count + mxnet_kvstore_pull_seconds.count
deltas; fused = mxnet_kvstore_fused_buckets_total (+ any fallback pushes).

Usage:
    python benchmark/comm_bench.py [--hidden 768] [--layers 12]
        [--vocab 30522] [--replicas 1] [--steps 10] [--warmup 2]
        [--bucket-mb 25] [--dtype float32] [--kvstore local]

Prints one JSON line per mode plus a summary:
    {"metric": "kvstore_dispatches_per_step", "mode": "fused", ...}
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def bert_shapes(hidden, layers, vocab, seq=512):
    """The dense parameter list of BERT-base ordered as the checkpoint lays
    it out: embeddings, then per-layer attention + FFN + LayerNorms, then
    the pooler.  ~199 tensors at the 12-layer default."""
    h, i4 = hidden, 4 * hidden
    shapes = [(vocab, h), (seq, h), (2, h), (h,), (h,)]  # embeds + emb LN
    for _ in range(layers):
        shapes += [
            (h, h), (h,), (h, h), (h,), (h, h), (h,),   # q, k, v
            (h, h), (h,), (h,), (h,),                   # attn out + LN
            (i4, h), (i4,), (h, i4), (h,),              # FFN in / out
            (h,), (h,),                                 # output LN
        ]
    shapes += [(h, h), (h,)]                            # pooler
    return shapes


def run_mode(kv, keys, grads, outs, steps, warmup):
    """Time `steps` pushpull_list calls; returns (host_s/step, wall_s/step,
    dispatches/step) with dispatches read from the telemetry registry."""
    from mxnet_tpu import nd, telemetry

    def counts():
        return (telemetry.histogram("mxnet_kvstore_push_seconds").count
                + telemetry.histogram("mxnet_kvstore_pull_seconds").count
                + telemetry.counter(
                    "mxnet_kvstore_fused_buckets_total").value)

    for _ in range(warmup):
        kv.pushpull_list(keys, grads, outs)
    nd.waitall()
    c0 = counts()
    host_s = 0.0
    t_wall = time.perf_counter()
    for _ in range(steps):
        t0 = time.perf_counter()
        kv.pushpull_list(keys, grads, outs)
        host_s += time.perf_counter() - t0
    nd.waitall()
    wall_s = time.perf_counter() - t_wall
    dispatches = (counts() - c0) / steps
    return host_s / steps, wall_s / steps, dispatches


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--hidden", type=int, default=768)
    ap.add_argument("--layers", type=int, default=12)
    ap.add_argument("--vocab", type=int, default=30522)
    ap.add_argument("--replicas", type=int, default=2)
    ap.add_argument("--steps", type=int, default=10)
    ap.add_argument("--warmup", type=int, default=2)
    ap.add_argument("--bucket-mb", type=float, default=25.0)
    ap.add_argument("--dtype", default="float32")
    ap.add_argument("--kvstore", default="local")
    args = ap.parse_args()

    import mxnet_tpu as mx
    from mxnet_tpu import nd, telemetry
    telemetry.enable()

    shapes = bert_shapes(args.hidden, args.layers, args.vocab)
    n_params = sum(int(np.prod(s)) for s in shapes)
    print(json.dumps({"metric": "param_tensors", "value": len(shapes),
                      "params": n_params,
                      "bytes": n_params * np.dtype(args.dtype).itemsize}))

    rng = np.random.RandomState(0)
    keys = list(range(len(shapes)))
    grads = []
    for s in shapes:
        reps = [nd.array(rng.standard_normal(s).astype(args.dtype),
                         ctx=mx.cpu(r % max(args.replicas, 1)))
                for r in range(args.replicas)]
        grads.append(reps if len(reps) > 1 else reps[0])

    results = {}
    for mode in ("perkey", "fused"):
        kv = mx.kv.create(args.kvstore)
        kv.set_bucket_size(0 if mode == "perkey" else args.bucket_mb)
        for k, g in zip(keys, grads):
            kv.init(k, g[0] if isinstance(g, list) else g)
        host, wall, disp = run_mode(kv, keys, grads, grads,
                                    args.steps, args.warmup)
        results[mode] = (host, wall, disp)
        print(json.dumps({
            "metric": "kvstore_allreduce", "mode": mode,
            "host_s_per_step": round(host, 6),
            "wall_s_per_step": round(wall, 6),
            "dispatches_per_step": disp,
        }))

    (h0, w0, d0), (h1, w1, d1) = results["perkey"], results["fused"]
    summary = {
        "metric": "fused_vs_perkey",
        "dispatch_ratio": round(d0 / max(d1, 1e-9), 2),
        "host_speedup": round(h0 / max(h1, 1e-9), 2),
        "wall_speedup": round(w0 / max(w1, 1e-9), 2),
        "fused_buckets": telemetry.counter(
            "mxnet_kvstore_fused_buckets_total").value // max(
                args.steps + args.warmup, 1),
        "pass_dispatch_5x": d0 / max(d1, 1e-9) >= 5.0,
    }
    print(json.dumps(summary))
    if not summary["pass_dispatch_5x"]:
        sys.exit(1)


if __name__ == "__main__":
    main()
