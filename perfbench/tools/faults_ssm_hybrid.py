"""Read, on the chip and at the cell's own size, what faults of the hybrid
Mamba-2 + attention model read in the comparison that decides ``correct``:
the plain reference with one thing broken, put in the program's place,
against the sound reference (``perfbench/tools/readings.py --seeds`` reads
the program itself, seed by seed).  The faults:

    norm_then_gate          the mixer's gated norm in the other order: a
                            norm per head, then the gate (Qwen3-Next's)
    state_not_carried       the scan's state starts at zero in every chunk
                            of ``mamba_chunk_size`` positions
    skip_left_out           ``D x`` left out of the scan's output
    residual_multiplier     both branches added at full weight (1 for 0.22)
    step_without_bias       ``dt = softplus(dt)``, no ``dt_bias``
    write_without_step      a position writes ``x B^T``, not ``dt x B^T``
    half_batch              the second half of the row's positions left out,
                            the mean taken over the first (the cell's batch
                            is one row)
    state_unchanged         the optimizer's update left out
    control_fp8             every matrix product with operands, results and
                            cotangents rounded to 3 mantissa bits

One process for all of them, since the sound reference is made once.  Not
part of a benchmark run.

    python3 -m perfbench.tools.faults_ssm_hybrid --workload <cell> \\
        --seed 1 --out faults_<cell>.jsonl
"""

import argparse
import contextlib
import gc
import json
import os
import statistics
import sys
import time

from perfbench import compare
from perfbench import run as harness
from perfbench.feed import TokenFeed
from perfbench.runners import train_step


def _norm_then_gate(ref, heads):
    """rmsnorm per head of ``y``, times ``w``, then times ``silu(z)``."""
    import jax

    def wrong(y, z, w, eps):
        b, s, inner = y.shape
        y = ref._norm(y.reshape(b, s, heads, inner // heads), 1.0, eps)
        return y.reshape(b, s, inner) * w * jax.nn.silu(z)
    return wrong


def _patches(ref, name, cfg):
    import jax
    chunk = cfg["mamba_chunk_size"]
    return {
        "norm_then_gate": {"_gated_norm": _norm_then_gate(
            ref, cfg["mamba_n_heads"])},
        "state_not_carried": {
            "_reach": lambda i, j: (i >= j) & (i // chunk == j // chunk)},
        "skip_left_out": {"_skip": lambda D, x: 0.0 * x},
        "step_without_bias": {
            "_step": lambda dt, dt_bias: jax.nn.softplus(dt)},
        "write_without_step": {"_write": lambda dt, x: x},
    }.get(name, {})


# the faults that change a key of the configuration instead of a function
# of the reference (``arguments`` has those that are arguments of
# ``train_steps``)
CONFIG_FAULTS = {"residual_multiplier": {"residual_multiplier": 1.0}}
FAULTS = ("norm_then_gate", "state_not_carried", "skip_left_out",
          "residual_multiplier", "step_without_bias", "write_without_step",
          "half_batch", "state_unchanged", "control_fp8")


@contextlib.contextmanager
def planted(ref, name, cfg):
    """The reference module with the fault ``name`` in it; yields the keys
    to change in the configuration (every fault changes one, so that the
    reference's jitted programs are traced again)."""
    patches = _patches(ref, name, cfg)
    change = dict(CONFIG_FAULTS.get(name, {}),
                  planted_fault=FAULTS.index(name) + 1)
    kept = {k: getattr(ref, k) for k in patches}
    for k, v in patches.items():
        setattr(ref, k, v)
    try:
        yield change
    finally:
        for k, v in kept.items():
            setattr(ref, k, v)


def arguments(ref, name, traffic):
    """What a fault that is an argument of ``train_steps`` passes."""
    return {"half_batch": {"positions": traffic["seq"] // 2},
            "state_unchanged": {"skip_update": True},
            "control_fp8": {"matmul": ref.FP8_MATMUL}}.get(name, {})


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

    def reference(config, **kw):
        t0 = time.perf_counter()
        obs = train_step.observe_reference(
            ref, config, cell, shapes, args.seed, cfg["run"]["dtype"],
            devices[0], *first, **kw)
        return obs, time.perf_counter() - t0

    sound, took = reference(cfg)
    row = {"workload": cell["name"], "seed": args.seed,
           "limits": cell["limits"], "reference_s": took,
           "ref_losses": sound["losses"]}
    floor = 1e-3 * statistics.median(sound["grad1"].values())
    row["grad1_under_floor"] = sorted(
        k for k, g in sound["grad1"].items() if g < floor)
    print(json.dumps({k: row[k] for k in ("reference_s", "ref_losses",
                                          "grad1_under_floor")}), flush=True)
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    for name in args.faults.split(","):
        with planted(ref, name, cfg) as change:
            obs, took = reference(dict(cfg, **change),
                                  **arguments(ref, name, traffic))
        nums = compare.numbers(obs, sound)
        ok, _rows = compare.judge(nums, cell["limits"])
        row[name] = dict(nums, correct=ok, seconds=took)
        print(json.dumps({name: row[name]}), flush=True)
        with open(args.out, "a") as out:    # a row a fault: a later one
            out.write(json.dumps(            # may not fit
                {k: row[k] for k in ("workload", "seed", name)}) + "\n")
        # a fault's program is not run again: its code leaves the device
        jax.clear_caches()
        gc.collect()
    with open(args.out, "a") as out:
        out.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
