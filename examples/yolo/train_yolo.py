#!/usr/bin/env python
"""Tiny YOLOv3 trained on synthetic shapes (BASELINE config 2's YOLOv3;
reference workflow: GluonCV scripts/detection/yolo/train_yolo3.py in
miniature).

Same synthetic task as the SSD lane (bright square = class 0, blob =
class 1; ground truth is the bounding box) so the two detection families
are directly comparable: backbone -> 3-scale heads; host-side
YOLOV3TargetGenerator makes STATIC dense targets (the TPU-first analog of
GluonCV's prefetched targets); YOLOV3Loss (BCE obj/center/cls + L2
log-wh); yolo3_decode + box_nms at eval.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))))


def synth_batch(rng, batch, size=64):
    imgs = np.zeros((batch, 3, size, size), np.float32)
    labels = np.full((batch, 1, 5), -1.0, np.float32)
    for i in range(batch):
        cls = rng.randint(0, 2)
        w = rng.randint(16, 32)
        x0 = rng.randint(0, size - w)
        y0 = rng.randint(0, size - w)
        if cls == 0:
            imgs[i, :, y0:y0 + w, x0:x0 + w] = 1.0
        else:
            yy, xx = np.mgrid[0:size, 0:size]
            m = ((yy - (y0 + w / 2)) ** 2 + (xx - (x0 + w / 2)) ** 2
                 <= (w / 2) ** 2)
            imgs[i, :, m] = 1.0
        labels[i, 0] = [cls, x0 / size, y0 / size,
                        (x0 + w) / size, (y0 + w) / size]
    return imgs, labels


# anchors tuned to the synthetic 16-32 px boxes, one triple per scale
_ANCHORS = (((24, 24), (32, 32), (40, 40)),
            ((16, 16), (20, 20), (28, 28)),
            ((8, 8), (10, 10), (14, 14)))


def run(batch=16, steps=60, lr=5e-3, size=64, log=True, seed=0):
    import mxnet_tpu as mx
    from mxnet_tpu import autograd, gluon
    from mxnet_tpu.gluon.model_zoo import yolo

    mx.random.seed(seed)
    rng = np.random.RandomState(seed)
    net = yolo.YOLOV3(
        backbone=yolo.Darknet(layers=(1, 1, 2, 2, 1),
                              channels=(8, 16, 32, 64, 128, 256)),
        classes=2, anchors=_ANCHORS, channels=(64, 32, 16))
    net.initialize(mx.init.Xavier())
    trainer = gluon.Trainer(net.collect_params(), "adam",
                            {"learning_rate": lr})
    gen = yolo.YOLOV3TargetGenerator(classes=2, anchors=_ANCHORS,
                                     input_size=size)
    loss_fn = yolo.YOLOV3Loss()

    losses = []
    t0 = time.time()
    for _ in range(steps):
        imgs, labels = synth_batch(rng, batch, size)
        targets = gen(labels)                       # host-side, numpy
        x = mx.nd.array(imgs)
        tg = [[mx.nd.array(t) for t in scale] for scale in targets]
        with autograd.record():
            preds = net(x)
            loss = loss_fn(mx.nd, preds, tg)
        loss.backward()
        trainer.step(batch)
        losses.append(float(loss.asnumpy()))

    # eval: decode + NMS, mean IoU of the top detection vs ground truth
    imgs, labels = synth_batch(rng, 16, size)
    preds = net(mx.nd.array(imgs))
    det = yolo.yolo3_decode(preds, anchors=_ANCHORS, input_size=size,
                            conf_thresh=0.01, topk=10)
    ious = []
    for i in range(len(imgs)):
        top = det[i, 0]
        if top[0] < 0:
            ious.append(0.0)
            continue
        gt = labels[i, 0, 1:]
        tl = np.maximum(top[2:4], gt[:2])
        br = np.minimum(top[4:6], gt[2:])
        inter = np.prod(np.maximum(br - tl, 0))
        union = (np.prod(np.maximum(top[4:6] - top[2:4], 0))
                 + np.prod(gt[2:] - gt[:2]) - inter)
        ious.append(float(inter / max(union, 1e-12)))
    rec = {"first_loss": round(losses[0], 4),
           "last_loss": round(losses[-1], 4),
           "mean_top_iou": round(float(np.mean(ious)), 4),
           "steps_per_sec": round(steps / (time.time() - t0), 2)}
    if log:
        print(json.dumps(rec))
    return rec


def main():
    p = argparse.ArgumentParser()
    p.add_argument("--platform", default=None, choices=["cpu"],
                   help="pin the jax platform IN-PROCESS, before "
                        "the backend initializes")
    p.add_argument("--steps", type=int, default=60)
    p.add_argument("--batch", type=int, default=16)
    a = p.parse_args()
    if a.platform or os.environ.get("JAX_PLATFORMS") == "cpu":
        import jax
        jax.config.update("jax_platforms", a.platform or "cpu")
    rec = run(batch=a.batch, steps=a.steps)
    return 0 if rec["last_loss"] < rec["first_loss"] else 1


if __name__ == "__main__":
    sys.exit(main())
