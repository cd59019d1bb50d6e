"""Read, on the chip and at the cell's own size, what faults of the hybrid
Gated DeltaNet + gated attention + MoE model read in the comparison that
decides ``correct``: the plain reference with one thing broken, put in the
program's place, against the sound reference (``perfbench/tools/readings.py``
reads the program itself, seed by seed).  The faults:

    decay_left_out          the state never decays (alpha = 1)
    beta_one                every write at full strength (beta = 1)
    state_not_carried       the state starts at zero in every chunk of 64
                            positions (a chunked scan that drops its carry)
    conv_left_out           q, k, v skip the causal convolution
    output_gate_left_out    the attention's context skips sigmoid(gate)
    shared_gate_left_out    the shared expert skips sigmoid(x w_g)
    top9_of_10              one expert fewer a token
    sigmoid_router          sigmoid scores where the model has softmax
    half_batch              the second half of the row's positions left out,
                            the mean taken over the first (the cell's batch
                            is one row)
    state_unchanged         the optimizer's update left out
    control_fp8             every matrix product with operands, results and
                            cotangents rounded to 3 mantissa bits

One process for all of them, since the sound reference is made once.  Not
part of a benchmark run.

    python3 -m perfbench.tools.faults_gdn_moe --workload <cell> --seed 1 \\
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

CHUNK = 64


def _recurrence_without_carry(sound, chunk):
    """``reference.recurrence`` run on every chunk of ``chunk`` positions
    as if it were a row of its own, a chunk after the other (all chunks as
    one batch would hold 128 rows' states at once)."""
    def recurrence(q, k, v, g, beta, matmul):
        import jax
        import jax.numpy as jnp
        b, s = q.shape[:2]
        if s % chunk:
            raise ValueError(f"{s} positions are no whole chunks of {chunk}")

        def chunks(x):      # (b, s, …) -> (chunks, b, chunk, …)
            return jnp.moveaxis(
                x.reshape((b, s // chunk, chunk) + x.shape[2:]), 1, 0)

        out = jax.lax.map(lambda xs: sound(*xs, matmul),
                          tuple(chunks(x) for x in (q, k, v, g, beta)))
        return jnp.moveaxis(out, 0, 1).reshape((b, s) + out.shape[3:])
    return recurrence


def _patches(ref, name, chunk):
    import jax
    import jax.numpy as jnp
    sound = ref._decay_and_strength

    def decay_and_strength(one_decay, one_beta):
        def planted(*args):
            g, beta = sound(*args)
            return (jnp.zeros_like(g) if one_decay else g,
                    jnp.ones_like(beta) if one_beta else beta)
        return planted

    return {
        "decay_left_out": {
            "_decay_and_strength": decay_and_strength(True, False)},
        "beta_one": {
            "_decay_and_strength": decay_and_strength(False, True)},
        "state_not_carried": {
            "recurrence": _recurrence_without_carry(ref.recurrence, chunk)},
        "conv_left_out": {"_causal_conv": lambda x, w: x},
        "output_gate_left_out": {"_output_gate": lambda ctx, gate: ctx},
        "shared_gate_left_out": {
            "_shared_gate": lambda x, w, matmul: jnp.float32(1.0)},
        "sigmoid_router": {"_scores": jax.nn.sigmoid},
    }.get(name, {})


# the fault that changes a key of the configuration instead of a function
# of the reference (``arguments`` has those that are arguments of
# ``train_steps``)
CONFIG_FAULTS = {"top9_of_10": {"num_experts_per_tok": 9}}
FAULTS = ("decay_left_out", "beta_one", "state_not_carried", "conv_left_out",
          "output_gate_left_out", "shared_gate_left_out", "top9_of_10",
          "sigmoid_router", "half_batch", "state_unchanged", "control_fp8")


@contextlib.contextmanager
def planted(ref, name, chunk=CHUNK):
    """The reference module with the fault ``name`` in it; yields the keys
    to change in the configuration (every fault changes one, so that the
    reference's jitted layer programs are traced again)."""
    patches = _patches(ref, name, chunk)
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
    with open(args.out, "a") as out:
        out.write(json.dumps(row) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
