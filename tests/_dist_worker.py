"""Worker body for the N-process dist_tpu_sync tests (run via
tools/launch.py; mirrors tests/nightly/dist_sync_kvstore.py exact-value
checks).  Not collected by pytest (no test_ prefix).

Every expected value is a closed form in N = num_workers, so the same
body runs the 2-process tier-1 test and the 4-process scaling test
(ISSUE 7 satellite) unchanged.  The 2-bit-compression section needs an
even N: ranks 0/1 drive the exact quantization pattern and every higher
rank pair pushes values that stay strictly inside the threshold band
(quantize to 0 in both rounds), keeping the wire sums N-independent."""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax

# pin the platform through jax.config as well as the env var (same
# belt-and-braces as tests/conftest.py)
if os.environ.get("JAX_PLATFORMS") == "cpu":
    jax.config.update("jax_platforms", "cpu")
    # multi-process computations on the CPU backend need a host
    # collectives implementation (ISSUE 3 satellite: this missing config
    # was the failure behind the 2-proc dist tier-1 flake — the psum
    # raised "Multiprocess computations aren't implemented on the CPU
    # backend"); must be set BEFORE backend initialization
    try:
        jax.config.update("jax_cpu_collectives_implementation", "gloo")
    except (AttributeError, ValueError):
        pass  # older jaxlib without gloo: the kvstore deadline bounds it

# distributed init MUST precede backend init (jax.distributed contract)
jax.distributed.initialize(
    coordinator_address=os.environ["MXNET_DIST_COORDINATOR"],
    num_processes=int(os.environ["MXNET_DIST_NUM_WORKERS"]),
    process_id=int(os.environ["MXNET_DIST_RANK"]))

import numpy as np  # noqa: E402

import mxnet_tpu as mx  # noqa: E402


def main():
    kv = mx.kv.create("dist_tpu_sync")
    N = kv.num_workers
    assert N == int(os.environ["MXNET_DIST_NUM_WORKERS"]), N
    rank = kv.rank
    shape = (3, 4)
    tri = N * (N + 1) // 2           # sum_r (r + 1)

    # 1. exact-value dense allreduce: each worker pushes rank+1 everywhere
    kv.init(3, mx.nd.zeros(shape))
    kv.push(3, mx.nd.array(np.full(shape, rank + 1.0, np.float32)))
    out = mx.nd.zeros(shape)
    kv.pull(3, out)
    np.testing.assert_allclose(out.asnumpy(), float(tri))

    # 2. second round with different values (checks no stale state)
    kv.push(3, mx.nd.array(np.full(shape, (rank + 1) * 10.0, np.float32)))
    kv.pull(3, out)
    np.testing.assert_allclose(out.asnumpy(), 10.0 * tri)

    # 3. rank-dependent structured values: position (i, j) gets
    #    sum_r (r + i + j) = N*(i + j) + N(N-1)/2
    base = np.add.outer(np.arange(3), np.arange(4)).astype(np.float32)
    kv.push(3, mx.nd.array(base + rank))
    kv.pull(3, out)
    np.testing.assert_allclose(out.asnumpy(),
                               N * base + N * (N - 1) / 2.0)

    # 4. barrier + multi-key list API
    kv.barrier()
    kv.init([5, 7], [mx.nd.zeros((2,)), mx.nd.zeros((2,))])
    kv.push([5, 7], [mx.nd.ones((2,)) * (rank + 1),
                     mx.nd.ones((2,)) * (rank + 5)])
    outs = [mx.nd.zeros((2,)), mx.nd.zeros((2,))]
    kv.pull([5, 7], outs)
    np.testing.assert_allclose(outs[0].asnumpy(), float(tri))
    np.testing.assert_allclose(outs[1].asnumpy(), float(tri + 4 * N))

    # 5. fused pushpull_list (ISSUE 2): the whole key list buckets into
    #    flat buffers and crosses processes as ONE psum per bucket
    kv.init([20, 21, 22], [mx.nd.zeros((3,)), mx.nd.zeros((2, 2)),
                           mx.nd.zeros((5,))])
    for rnd in range(2):  # second round re-uses the cached plan/executables
        vals = [mx.nd.ones((3,)) * (rank + 1 + rnd),
                mx.nd.ones((2, 2)) * (rank + 2 + rnd),
                mx.nd.ones((5,)) * (rank + 3 + rnd)]
        outs = [mx.nd.zeros((3,)), mx.nd.zeros((2, 2)), mx.nd.zeros((5,))]
        kv.pushpull_list([20, 21, 22], vals, outs)
        np.testing.assert_allclose(outs[0].asnumpy(),
                                   float(tri + N * rnd))
        np.testing.assert_allclose(outs[1].asnumpy(),
                                   float(tri + N * (1 + rnd)))
        np.testing.assert_allclose(outs[2].asnumpy(),
                                   float(tri + N * (2 + rnd)))
    assert kv._bucketer is not None and kv._bucketer.builds == 2  # 1 bucket

    # 6. 2-bit compression over the wire (packed allgather path), exact
    #    values at threshold t=0.5.  Ranks 0/1 replay the canonical
    #    pattern: +0.7 → +t / -0.6 → -t (sum 0), then residual-fed
    #    0.2+0.4 → +t / -0.1-0.3 → 0 (sum +t).  Ranks >= 2 push ±0.1
    #    then ±0.1 again: accumulated ±0.2 never crosses t, so they
    #    quantize to 0 BOTH rounds and the sums stay N-independent.
    assert N % 2 == 0, "2-bit section is designed for even N"
    kv2 = mx.kv.create("dist_tpu_sync")
    kv2.set_gradient_compression({"type": "2bit", "threshold": 0.5})
    shape2 = (2, 3)
    kv2.init(11, mx.nd.zeros(shape2))
    if rank == 0:
        first, second = 0.7, 0.4
    elif rank == 1:
        first, second = -0.6, -0.3
    else:
        first = second = 0.1 if rank % 2 == 0 else -0.1
    kv2.push(11, mx.nd.array(np.full(shape2, first, np.float32)))
    out2 = mx.nd.zeros(shape2)
    kv2.pull(11, out2)
    np.testing.assert_allclose(out2.asnumpy(), 0.0)
    kv2.push(11, mx.nd.array(np.full(shape2, second, np.float32)))
    kv2.pull(11, out2)
    np.testing.assert_allclose(out2.asnumpy(), 0.5)

    print(f"worker {rank}/{N}: OK", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
