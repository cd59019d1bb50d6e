"""Read, on the chip and at the cell's own size, what faults of the looped
language model read in the comparison that decides ``correct``: the plain
reference with one thing broken, put in the program's place, against the
sound reference (``perfbench/tools/readings.py --seeds`` reads the program
itself, seed by seed).  The faults:

    three_loop_steps        the stack applied three times for four
    first_uses_no_grad      the layers' weights take no gradient from their
                            first three uses (a weight's gradient is the
                            last application's alone)
    final_norm_not_carried  loop step t + 1 starts from the stack's output
                            before the final norm (head and gate still read
                            the normed one)
    post_norms_left_out     N2 and N4 left out: a plain pre-norm layer
    last_exit_alone         the loss is the last exit's cross-entropy
    entropy_left_out        beta = 0
    p_uniform               every exit weighs a quarter, whatever the gate
    half_batch              the second half of the row's positions left out,
                            the mean taken over the first (the cell's batch
                            is one row)
    state_unchanged         the optimizer's update left out
    control_fp8             every matrix product with operands, results and
                            cotangents rounded to 3 mantissa bits

One process for all of them, since the sound reference is made once.  Not
part of a benchmark run.

    python3 -m perfbench.tools.faults_loop_lm --workload <cell> --seed 1 \\
        --out chiprun_out/faults_<cell>.jsonl
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


def _gradient_if():
    """``(w, keep) -> w`` whose cotangent is ``keep`` times what came: the
    forward copies nothing (a ``where`` over the weights would hold a
    second set of them a loop step)."""
    import jax

    @jax.custom_vjp
    def gradient_if(w, keep):
        return w

    gradient_if.defvjp(lambda w, keep: (w, keep),
                       lambda keep, ct: (ct * keep.astype(ct.dtype), None))
    return gradient_if


def _patches(ref, name):
    import jax
    import jax.numpy as jnp
    gradient_if = _gradient_if()
    return {
        "first_uses_no_grad": {
            "_weights_of_use": lambda params, use, uses: jax.tree.map(
                lambda w: gradient_if(w, use == uses - 1), params)},
        "final_norm_not_carried": {"_carried": lambda normed, raw: raw},
        "post_norms_left_out": {"_post_norm": lambda x, w, eps: x},
        "last_exit_alone": {"position_loss": lambda p, ce, beta: ce[-1]},
        "p_uniform": {
            "exit_distribution": lambda lam:
                jnp.full_like(lam, 1.0 / lam.shape[0])},
    }.get(name, {})


# the faults that change a key of the configuration instead of a function
# of the reference (``arguments`` has those that are arguments of
# ``train_steps``)
CONFIG_FAULTS = {"three_loop_steps": {"total_ut_steps": 3},
                 "entropy_left_out": {"exit_entropy_beta": 0.0}}
FAULTS = ("three_loop_steps", "first_uses_no_grad", "final_norm_not_carried",
          "post_norms_left_out", "last_exit_alone", "entropy_left_out",
          "p_uniform", "half_batch", "state_unchanged", "control_fp8")


@contextlib.contextmanager
def planted(ref, name):
    """The reference module with the fault ``name`` in it; yields the keys
    to change in the configuration (every fault changes one, so that the
    reference's jitted loss is traced again)."""
    patches = _patches(ref, name)
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
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
    for name in args.faults.split(","):
        with planted(ref, name) as change:
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
