"""Input-pipeline throughput benchmark.

Measures images/sec through ``ImageRecordIter`` on REAL JPEG bytes — the
reference measures its decode thread pool the same way
(src/io/iter_image_recordio_2.cc ParseChunk; SURVEY N19, §3.5).  The
ResNet-50 bf16 bench lane runs ~1000 img/s on the v5e chip, so the
pipeline must sustain >= ~1500 img/s (1.5x) to never starve training.

Usage:
    python benchmark/io_bench.py [--images 2048] [--size 256]
        [--threads 1,4,8] [--batch 128]

Prints one JSON line per thread count plus a summary line:
    {"metric": "image_record_iter_images_per_sec", "value": ..., ...}
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


def make_dataset(path_prefix, n_images, size, quality=90, seed=0):
    """Pack n random JPEGs (noise + structure, realistic compressed size)
    into an indexed RecordIO pair — the im2rec output format."""
    import cv2
    from mxnet_tpu import recordio
    rec = recordio.MXIndexedRecordIO(path_prefix + ".idx",
                                     path_prefix + ".rec", "w")
    r = np.random.RandomState(seed)
    for i in range(n_images):
        # low-freq structure + noise: compresses like a natural photo
        base = cv2.resize(r.randint(0, 255, (16, 16, 3), np.uint8),
                          (size, size), interpolation=cv2.INTER_CUBIC)
        noise = r.randint(0, 40, (size, size, 3), np.uint8)
        img = np.clip(base.astype(np.int32) + noise, 0, 255).astype(np.uint8)
        ok, buf = cv2.imencode(".jpg", img,
                               [cv2.IMWRITE_JPEG_QUALITY, quality])
        assert ok
        header = recordio.IRHeader(0, float(i % 1000), i, 0)
        rec.write_idx(i, recordio.pack(header, buf.tobytes()))
    rec.close()
    return path_prefix + ".rec"


def measure(rec_path, batch, threads, crop=224, epochs=2, decoder="threads"):
    import mxnet_tpu as mx
    from mxnet_tpu.io import ImageRecordIter
    # ctx=cpu: meter the PIPELINE (read+decode+augment+collate), not the
    # host->device link — the training bench measures compute the same way
    it = ImageRecordIter(path_imgrec=rec_path, data_shape=(3, crop, crop),
                        batch_size=batch, rand_crop=True, rand_mirror=True,
                        preprocess_threads=threads, decoder=decoder,
                        ctx=mx.cpu(),
                        mean_r=123.68, mean_g=116.78, mean_b=103.94,
                        std_r=58.4, std_g=57.1, std_b=57.4)
    # warmup epoch (page cache, pool spin-up), then timed epochs
    n = 0
    for batch_data in it:
        n += batch_data.data[0].shape[0]
    t0 = time.perf_counter()
    m = 0
    for _ in range(epochs):
        it.reset()
        for batch_data in it:
            m += batch_data.data[0].shape[0]
    dt = time.perf_counter() - t0
    it.close()
    return m / dt


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--images", type=int, default=2048)
    ap.add_argument("--size", type=int, default=256)
    ap.add_argument("--batch", type=int, default=128)
    ap.add_argument("--threads", default="1,4,8")
    ap.add_argument("--decoder", default="threads",
                    choices=["threads", "processes"])
    ap.add_argument("--target", type=float, default=1500.0,
                    help="img/s the training step needs (1.5x ResNet-50)")
    args = ap.parse_args(argv)

    with tempfile.TemporaryDirectory() as td:
        rec = make_dataset(os.path.join(td, "bench"), args.images,
                           args.size)
        rec_mb = os.path.getsize(rec) / 1e6
        best = 0.0
        for t in [int(x) for x in args.threads.split(",")]:
            ips = measure(rec, args.batch, t, decoder=args.decoder)
            best = max(best, ips)
            print(json.dumps({
                "metric": "image_record_iter_images_per_sec",
                "value": round(ips, 1), "unit": "images/s",
                "vs_baseline": round(ips / args.target, 4),
                "extra": {"threads": t, "decoder": args.decoder,
                          "batch": args.batch, "images": args.images,
                          "jpeg_size": args.size,
                          "rec_mb": round(rec_mb, 1),
                          "host_cores": os.cpu_count()}}))
        print(json.dumps({
            "metric": "image_record_iter_best_images_per_sec",
            "value": round(best, 1), "unit": "images/s",
            "vs_baseline": round(best / args.target, 4),
            "extra": {"host_cores": os.cpu_count(),
                      "note": "decode scales with cores (thread pool, cv2 "
                              "releases the GIL; --decoder processes for "
                              "GIL-bound augment tails); single-core rate "
                              "x cores bounds a multi-core host"}}))
        return 0 if best >= args.target else 1


if __name__ == "__main__":
    sys.exit(main())
