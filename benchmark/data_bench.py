"""Decode-pool throughput gate (ISSUE 7 acceptance lane).

Measures the multi-core shared-memory decode pipeline
(``ImageRecordIter(preprocess_threads=N, decoder='pool')`` →
io/pipeline.py) against single-process decode on the SAME RecordIO pack
of real JPEG bytes, and gates on the RATIO — an absolute img/s floor
would flake on CI-host variance, a ratio can't.

Methodology: single and pooled epochs run INTERLEAVED (A/B/A/B...) and
the gate ratio is the MEDIAN OF PAIRED per-trial ratios p[i]/s[i] —
CI-class hosts drift tens of percent within a run (page cache, CPU
burst credits), so medians of independent blocks still compare
different throttle states; adjacent A/B pairs see the same one and the
drift cancels in the ratio.  Worker count is clamped to the host's
cores (extra workers on a small host only add contention and measure
oversubscription, not the pipeline).  Correctness rides along: the
first pooled epoch must be bit-identical to the single epoch (same
seed → same shuffle, same per-index augmentation draws).

Gate: pooled/single >= 2.0 on hosts with >= 4 cores (the CI runner
class and the ISSUE 7 acceptance bar — a 4-worker pool must at least
double single-core decode).  Hosts with fewer cores cannot physically
double (workers + the assembler + the consumer share the cores), so the
gate relaxes to 0.6 x usable cores; the measured ratio is always
printed.

Usage:
    python benchmark/data_bench.py [--images 768] [--size 256]
        [--batch 64] [--workers 4] [--trials 3]
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

from io_bench import make_dataset  # noqa: E402 — shared dataset generator


def _make_iter(rec_path, batch, threads, crop, seed):
    import mxnet_tpu as mx
    return mx.io.ImageRecordIter(
        path_imgrec=rec_path, data_shape=(3, crop, crop), batch_size=batch,
        shuffle=True, rand_crop=True, rand_mirror=True, seed=seed,
        preprocess_threads=threads, decoder="pool", ctx=mx.cpu(),
        mean_r=123.68, mean_g=116.78, mean_b=103.94,
        std_r=58.4, std_g=57.1, std_b=57.4)


def _epoch_rate(it, collect=None):
    it.reset()
    n = 0
    t0 = time.perf_counter()
    for b in it:
        n += b.data[0].shape[0]
        if collect is not None:
            collect.append((b.data[0].asnumpy(), b.label[0].asnumpy()))
    return n / (time.perf_counter() - t0)


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--images", type=int, default=768)
    ap.add_argument("--size", type=int, default=256)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--crop", type=int, default=224)
    ap.add_argument("--workers", type=int, default=4)
    ap.add_argument("--trials", type=int, default=5)
    args = ap.parse_args(argv)

    cores = os.cpu_count() or 1
    # honest clamp: never more workers than cores (a forced 2-worker pool
    # on a 1-core host measures time-slicing and makes its own 1.2x gate
    # physically unattainable)
    workers = max(1, min(args.workers, cores))
    gate = 2.0 if cores >= 4 else 0.6 * workers

    with tempfile.TemporaryDirectory() as td:
        rec = make_dataset(os.path.join(td, "bench"), args.images, args.size)
        single = _make_iter(rec, args.batch, 1, args.crop, seed=7)
        pooled = _make_iter(rec, args.batch, workers, args.crop, seed=7)

        # correctness guard: pooled epoch 1 == single epoch 1, bitwise.
        # (Also serves as both iterators' warmup: pool spin-up, page cache.)
        ref, got = [], []
        _epoch_rate(single, collect=ref)
        _epoch_rate(pooled, collect=got)
        assert len(ref) == len(got) > 0
        for (rd, rl), (gd, gl) in zip(ref, got):
            # epoch counters advanced in lockstep (one reset each), so the
            # shuffle orders and per-index augmentation seeds line up
            np.testing.assert_array_equal(rd, gd)
            np.testing.assert_array_equal(rl, gl)

        s_rates, p_rates = [], []
        for _ in range(args.trials):
            s_rates.append(_epoch_rate(single))
            p_rates.append(_epoch_rate(pooled))
        single.close()
        pooled.close()

    s_med, p_med = float(np.median(s_rates)), float(np.median(p_rates))
    pair_ratios = [p / s for s, p in zip(s_rates, p_rates)]
    ratio = float(np.median(pair_ratios))
    print(json.dumps({
        "metric": "data_bench_single_process_images_per_sec",
        "value": round(s_med, 1), "unit": "images/s",
        "extra": {"trials": [round(x, 1) for x in s_rates]}}))
    print(json.dumps({
        "metric": "data_bench_pooled_images_per_sec",
        "value": round(p_med, 1), "unit": "images/s",
        "vs_baseline": round(ratio, 4),
        "extra": {"workers": workers, "host_cores": cores,
                  "batch": args.batch, "images": args.images,
                  "trials": [round(x, 1) for x in p_rates],
                  "paired_ratios": [round(r, 2) for r in pair_ratios],
                  "bit_identical": True, "gate": round(gate, 2)}}))
    if ratio < gate:
        print(f"FAIL: pooled/single {ratio:.2f}x < gate {gate:.2f}x "
              f"({workers} workers, {cores} cores)", file=sys.stderr)
        return 1
    print(f"PASS: pooled decode {ratio:.2f}x single-process "
          f"(gate {gate:.2f}x, {workers} workers, {cores} cores)")
    return 0


if __name__ == "__main__":
    sys.exit(main())
