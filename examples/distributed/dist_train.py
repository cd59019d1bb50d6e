"""Distributed data-parallel training over ``dist_tpu_sync`` — the
reference's ``example/image-classification --kv-store dist_sync`` workflow
(launched by ``tools/launch.py``, SURVEY §3.4) rebuilt TPU-native: no
parameter-server processes, gradients allreduce over the jax.distributed
process mesh via a compiled psum (``mxnet_tpu/kvstore/dist.py``).

Run (2 localhost workers on virtual CPU devices):

    python tools/launch.py -n 2 --cpu-devices 1 \
        python examples/distributed/dist_train.py

Each worker:
 1. bootstraps jax.distributed from the MXNET_DIST_* env the launcher set,
 2. proves EXACT grad-sum semantics through the kvstore (push rank-scaled
    values, pull the cross-worker sum — the dist-kvstore oracle from
    tests/nightly/dist_sync_kvstore.py),
 3. trains an MLP with ``gluon.Trainer(..., kvstore='dist_tpu_sync')`` on
    its own shard of a synthetic classification set and asserts the loss
    drops — identical params on every worker after every step (data
    parallelism over processes).
"""

from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))

import jax

if os.environ.get("JAX_PLATFORMS") == "cpu":
    # a launcher's site hooks may have imported jax already: pin
    # through jax.config as well
    jax.config.update("jax_platforms", "cpu")
    # multi-process computations on the CPU backend need a host
    # collectives implementation; must precede backend initialization
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except (AttributeError, ValueError):
        pass  # older jaxlib without gloo

if "MXNET_DIST_COORDINATOR" in os.environ:
    # distributed init MUST precede backend init (jax.distributed contract)
    jax.distributed.initialize(
        coordinator_address=os.environ["MXNET_DIST_COORDINATOR"],
        num_processes=int(os.environ["MXNET_DIST_NUM_WORKERS"]),
        process_id=int(os.environ["MXNET_DIST_RANK"]))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402
from mxnet_tpu import autograd, gluon  # noqa: E402


def _assert_grad_sum(kv):
    """Exact-value allreduce check: worker r pushes full(r+1); the pulled
    value must be sum_{r<n}(r+1) on EVERY worker."""
    n = kv.num_workers
    shape = (4, 5)
    kv.init("oracle", mx.nd.zeros(shape))
    kv.push("oracle", mx.nd.array(
        np.full(shape, kv.rank + 1.0, np.float32)))
    out = mx.nd.zeros(shape)
    kv.pull("oracle", out)
    want = n * (n + 1) / 2.0
    np.testing.assert_allclose(out.asnumpy(), want)
    return want


def run(steps=30, batch_size=32, lr=0.1, hidden=64, classes=5,
        in_dim=20, log=True):
    net = gluon.nn.HybridSequential()
    with net.name_scope():
        net.add(gluon.nn.Dense(hidden, activation="relu", in_units=in_dim))
        net.add(gluon.nn.Dense(classes, in_units=hidden))
    # identical init everywhere: data parallelism requires all workers to
    # start from the same point (the kvstore sums GRADIENTS, not params)
    mx.random.seed(42)
    net.initialize(mx.initializer.Xavier())

    trainer = gluon.Trainer(net.collect_params(), "sgd",
                            {"learning_rate": lr},
                            kvstore="dist_tpu_sync")
    kv = trainer._kvstore if trainer._kvstore is not None \
        else mx.kv.create("dist_tpu_sync")
    rank, n = kv.rank, kv.num_workers
    oracle = _assert_grad_sum(kv)

    # per-rank shard of one fixed synthetic problem (separable blobs)
    r = np.random.RandomState(1234)          # SAME dataset on all ranks
    centers = r.randn(classes, in_dim) * 3.0
    xs = np.concatenate([centers[c] + r.randn(200, in_dim)
                         for c in range(classes)])
    ys = np.repeat(np.arange(classes), 200)
    perm = r.permutation(len(xs))
    xs, ys = xs[perm], ys[perm]
    xs, ys = xs[rank::n], ys[rank::n]        # disjoint shards per worker

    loss_fn = gluon.loss.SoftmaxCrossEntropyLoss()
    hist = []
    for step in range(steps):
        lo = (step * batch_size) % (len(xs) - batch_size)
        x = mx.nd.array(xs[lo:lo + batch_size].astype(np.float32))
        y = mx.nd.array(ys[lo:lo + batch_size].astype(np.float32))
        with autograd.record():
            loss = loss_fn(net(x), y)
        loss.backward()
        # global batch = batch_size * n (the kvstore sums grads; Trainer
        # rescales by the batch size passed here)
        trainer.step(batch_size * n)
        hist.append(float(loss.mean().asnumpy()))
        if log and rank == 0 and step % 10 == 0:
            print(f"step {step}: loss {hist[-1]:.4f}", flush=True)

    assert hist[-1] < hist[0], (hist[0], hist[-1])
    if log:
        print(f"worker {rank}/{n}: grad-sum oracle {oracle}, "
              f"loss {hist[0]:.4f} -> {hist[-1]:.4f} OK", flush=True)
    return hist


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=30)
    ap.add_argument("--batch-size", type=int, default=32)
    ap.add_argument("--lr", type=float, default=0.1)
    args = ap.parse_args(argv)
    run(steps=args.steps, batch_size=args.batch_size, lr=args.lr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
