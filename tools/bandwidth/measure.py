#!/usr/bin/env python
"""Allreduce bandwidth oracle (reference tools/bandwidth/measure.py —
the BASELINE "KVStore allreduce BW" metric).

Measures the kvstore reduction path at increasing sizes and reports
algorithm bandwidth per the standard allreduce accounting
``algbw = 2 * (n-1)/n * bytes / time`` (ring-allreduce wire traffic).

Modes (auto-selected):
 - multi-process (launched under tools/launch.py): dist_tpu_sync psum
   over the process mesh — what a TPU pod slice does over ICI/DCN.
 - single process, multi-device: parallel.allreduce over the local mesh
   (the 'device'-kvstore path; virtual 8-CPU mesh in tests).
 - single device: reports device memory bandwidth of the reduce path
   (n=1 — no collective; printed with "devices": 1 so consumers can
   discount it).

Output: one JSON line per size + a summary line, e.g.
  {"metric": "allreduce_bw", "size_mb": 64.0, "gbps": 12.3, ...}
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def _maybe_init_distributed():
    """Join the jax.distributed rendezvous when launched by tools/launch.py
    (must happen before any backend query like process_count)."""
    import jax
    # honor JAX_PLATFORMS explicitly: a "cpu" request silently landing
    # on the TPU would fake the multi-device measurement
    if os.environ.get("JAX_PLATFORMS") == "cpu":
        jax.config.update("jax_platforms", "cpu")
        # multi-process CPU collectives need a host implementation,
        # configured BEFORE backend init (the ISSUE 3 dist-worker fix;
        # without it every cross-process psum raises)
        try:
            jax.config.update("jax_cpu_collectives_implementation", "gloo")
        except (AttributeError, ValueError):
            pass  # older jaxlib without gloo
    coord = os.environ.get("MXNET_DIST_COORDINATOR")
    if coord:
        try:
            jax.distributed.initialize(
                coordinator_address=coord,
                num_processes=int(os.environ["MXNET_DIST_NUM_WORKERS"]),
                process_id=int(os.environ["MXNET_DIST_RANK"]))
        except RuntimeError:
            pass  # already initialized


def measure(sizes_mb, iters=5, use_dist=None):
    import jax
    import numpy as np
    import mxnet_tpu as mx
    from mxnet_tpu import parallel

    _maybe_init_distributed()
    n_proc = jax.process_count()
    dist = use_dist if use_dist is not None else n_proc > 1
    rows = []
    if dist:
        kv = mx.kv.create("dist_tpu_sync")
        n = kv.num_workers
        reduce_arr = kv._allreduce
    else:
        mesh = parallel.make_mesh()
        n = mesh.size

        def reduce_arr(arr):
            out = parallel.allreduce([mx.nd.NDArray._from_data(arr)],
                                     mesh=mesh)
            return out[0]._data

    for mb in sizes_mb:
        elems = int(mb * 1024 * 1024 / 4)
        arr = jax.numpy.asarray(np.random.randn(elems).astype(np.float32))
        reduce_arr(arr)  # warmup/compile
        t0 = time.perf_counter()
        for _ in range(iters):
            out = reduce_arr(arr)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / iters
        nbytes = elems * 4
        factor = 2 * (n - 1) / n if n > 1 else 1.0
        algbw = factor * nbytes / dt / 1e9
        rows.append({"metric": "allreduce_bw", "size_mb": mb,
                     "gbps": round(algbw, 3), "time_ms": round(dt * 1e3, 3),
                     "devices": n, "mode": "dist" if dist else "local"})
    return rows


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--sizes-mb", default="1,4,16,64",
                    help="comma-separated message sizes in MB")
    ap.add_argument("--iters", type=int, default=5)
    args = ap.parse_args(argv)
    sizes = [float(s) for s in args.sizes_mb.split(",") if s]
    rows = measure(sizes, args.iters)  # initializes distributed if launched
    import jax
    if jax.process_index() == 0:
        for r in rows:
            print(json.dumps(r))
        best = max(rows, key=lambda r: r["gbps"])
        print(json.dumps({"metric": "allreduce_bw_peak",
                          "value": best["gbps"], "unit": "GB/s",
                          "size_mb": best["size_mb"],
                          "devices": best["devices"]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
