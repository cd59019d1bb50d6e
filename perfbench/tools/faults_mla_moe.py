"""Read, on the chip and at the cell's own size, what faults of the MLA + MoE
model read in the comparison that decides ``correct``: the plain reference
with one thing broken, put in the program's place, against the sound
reference (as ``perfbench/tools/readings.py`` does for the faults any
training cell can have).  The faults:

    rope_not_interleaved        RoPE turns the pairs (i, i + d/2) of q's and
                                k's rope slice, not the published (2i, 2i+1)
    attn_dq_diagonal_skipped    the attention's backward leaves the key
                                block on the diagonal out of dq (a causal
                                streaming kernel whose block loop stops one
                                short); forward, dk and dv are sound
    attn_dkv_diagonal_skipped   the same block left out of dk and dv
    scaling_left_out            routed_scaling_factor 1
    top5_of_6                   one expert fewer a token

One process for all of them, since the sound reference is made once.  Not
part of a benchmark run.

    python3 -m perfbench.tools.faults_mla_moe --workload <cell> --seed 1 \\
        --out chiprun_out/faults_<cell>.jsonl
"""

import argparse
import contextlib
import json
import os
import statistics
import sys
import time

from perfbench import compare
from perfbench import run as harness
from perfbench.feed import TokenFeed
from perfbench.runners import train_step

BLOCK = 512     # rows of a key block of the streaming kernels at seq 4096


def _rope_halves(x, theta):
    """RoPE of the rotate-half form WITHOUT the de-interleave before it:
    pair i is (x[i], x[i + d/2])."""
    import jax.numpy as jnp
    seq, d = x.shape[-2], x.shape[-1]
    freq = theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    angle = jnp.arange(seq, dtype=jnp.float32)[:, None] * freq[None]
    a, b = x[..., :d // 2], x[..., d // 2:]
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.concatenate([a * cos - b * sin, a * sin + b * cos], -1)


def _attend_with_block_skipped(sound, skip_dq, skip_dkv, block):
    """``reference._attend`` with a backward written out, in which the
    (query, key) pairs of one block on the diagonal are left out of dq, or
    of dk and dv.  The forward is the sound one."""
    import jax
    import jax.numpy as jnp

    def make(matmul):
        @jax.custom_vjp
        def attend(q, k, v, start):
            return sound(q, k, v, start, matmul)

        def fwd(q, k, v, start):
            return attend(q, k, v, start), (q, k, v, start)

        def bwd(res, d_out):
            q, k, v, start = res
            scale = 1.0 / jnp.sqrt(jnp.float32(q.shape[-1]))
            row = start + jnp.arange(q.shape[2])[:, None]
            col = jnp.arange(k.shape[2])[None]
            scores = matmul("bhqd,bhkd->bhqk", q, k) * scale
            p = jax.nn.softmax(jnp.where(row >= col, scores, -jnp.inf), -1)
            d_p = matmul("bhqd,bhkd->bhqk", d_out, v)
            d_s = p * (d_p - (p * d_p).sum(-1, keepdims=True)) * scale
            kept = (row // block != col // block)
            s_q = jnp.where(kept, d_s, 0.0) if skip_dq else d_s
            s_kv = jnp.where(kept, d_s, 0.0) if skip_dkv else d_s
            p_kv = jnp.where(kept, p, 0.0) if skip_dkv else p
            return (matmul("bhqk,bhkd->bhqd", s_q, k),
                    matmul("bhqk,bhqd->bhkd", s_kv, q),
                    matmul("bhqk,bhqd->bhkd", p_kv, d_out), None)

        attend.defvjp(fwd, bwd)
        return attend

    def attend(q, k, v, start, matmul):
        return make(matmul)(q, k, v, start)
    return attend


@contextlib.contextmanager
def planted(ref, name, block=BLOCK):
    """The reference module with the fault ``name`` in it; yields the keys
    to change in the configuration (every fault changes one, so that the
    reference's jitted layer programs are traced again)."""
    patches, change = {}, {"planted_fault": sorted(FAULTS).index(name) + 1}
    if name == "rope_not_interleaved":
        patches["_rope"] = _rope_halves
    elif name.startswith("attn_"):
        patches["_attend"] = _attend_with_block_skipped(
            ref._attend, name == "attn_dq_diagonal_skipped",
            name == "attn_dkv_diagonal_skipped", block)
        # the written-out backward holds a few more (rows, seq) arrays a
        # head than the sound one: half the query rows at a time
        patches["_QUERY_ROWS"] = max(block, ref._QUERY_ROWS // 2)
    else:
        change.update(FAULTS[name])
    kept = {k: getattr(ref, k) for k in patches}
    for k, v in patches.items():
        setattr(ref, k, v)
    try:
        yield change
    finally:
        for k, v in kept.items():
            setattr(ref, k, v)


FAULTS = {"rope_not_interleaved": None, "attn_dq_diagonal_skipped": None,
          "attn_dkv_diagonal_skipped": None,
          "scaling_left_out": {"routed_scaling_factor": 1.0},
          "top5_of_6": {"num_experts_per_tok": 5}}


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--faults", default=",".join(FAULTS))
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    import mxnet_tpu  # noqa: F401
    _bench, cell = harness.load_cell(args.workload)
    devices = jax.devices()
    harness.check_devices(devices, cell["chips"],
                          harness.load_json(harness.HERE, "peaks.json"))
    cfg, traffic = cell["config"], cell["traffic"]
    ref, _builder, shapes = train_step.sides(cfg)
    first = TokenFeed(traffic, cfg["vocab_size"], args.seed).next()

    def reference(config):
        t0 = time.perf_counter()
        obs = train_step.observe_reference(
            ref, config, cell, shapes, args.seed, cfg["run"]["dtype"],
            devices[0], *first)
        return obs, time.perf_counter() - t0

    sound, took = reference(cfg)
    row = {"workload": cell["name"], "seed": args.seed,
           "limits": cell["limits"], "reference_s": took,
           "ref_losses": sound["losses"]}
    floor = 1e-3 * statistics.median(sound["grad1"].values())
    row["grad1_under_floor"] = sorted(
        k for k, g in sound["grad1"].items() if g < floor)
    for name in args.faults.split(","):
        with planted(ref, name) as change:
            obs, took = reference(dict(cfg, **change))
        nums = compare.numbers(obs, sound)
        ok, _rows = compare.judge(nums, cell["limits"])
        row[name] = dict(nums, correct=ok, seconds=took)
        print(json.dumps({name: row[name]}), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    with open(args.out, "a") as out:
        out.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
