"""Read, on the chip and at a cell's own size, the numbers its limits are
set from (PERF.md, "How correct is decided"): for each seed the program's
first dispatch against the plain reference (the lower reading), the
reference computed with fp8 matrix products in the program's place (the
control), and the faults a training cell can have, planted in the reference
put in the program's place: half of the batch left out with the mean taken
over the rest, and on a mesh the exchange between chips left out (one
chip's rows alone).  A state left unchanged reads 1 by construction.  One
process for all seeds, since set-up is most of a run.  Not part of a
benchmark run.

    python3 -m perfbench.tools.readings --workload <cell> --seeds 1,2,3 \\
        --control-seeds 1,2,3 --out chiprun_out/readings_<cell>.jsonl
"""

import argparse
import gc
import json
import os
import sys
import time

from perfbench import compare
from perfbench import run as harness
from perfbench import weights
from perfbench.runners import train_step
from perfbench.feed import TokenFeed


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--out", required=True)
    args = ap.parse_args(argv)
    import jax
    import mxnet_tpu  # noqa: F401
    _bench, cell = harness.load_cell(args.workload)
    devices = jax.devices()
    harness.check_devices(devices, cell["chips"],
                          harness.load_json(harness.HERE, "peaks.json"))
    cfg, traffic = cell["config"], cell["traffic"]
    ref, builder, shapes = train_step.sides(cfg)
    dtype, dev0 = cfg["run"]["dtype"], devices[0]
    seeds = [int(s) for s in args.seeds.split(",")]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)

    def reference(seed, first, **kw):
        return train_step.observe_reference(
            ref, cfg, cell, shapes, seed, dtype, dev0, *first, **kw)

    with open(args.out, "a") as out:
        for seed in seeds:
            t0 = time.perf_counter()
            first = TokenFeed(traffic, cfg["vocab_size"], seed).next()
            program = builder.Program(
                cfg, traffic,
                weights.make_weights(shapes, seed, dtype, dev0), devices)
            losses = program.losses(program.run(*first))
            obs = train_step.observe_program(program, shapes, seed, dtype,
                                             dev0, losses)
            program.close()
            del program
            gc.collect()
            t1 = time.perf_counter()
            ref_obs = reference(seed, first)
            t2 = time.perf_counter()
            row = {"workload": cell["name"], "seed": seed,
                   "program": compare.numbers(obs, ref_obs),
                   "losses": obs["losses"], "ref_losses": ref_obs["losses"],
                   "program_s": t1 - t0, "reference_s": t2 - t1}
            if seed in controls:
                row["control_fp8"] = compare.numbers(
                    reference(seed, first, matmul=ref.FP8_MATMUL), ref_obs)
                row["fault_half_batch"] = compare.numbers(
                    reference(seed, first, rows=traffic["batch"] // 2),
                    ref_obs)
                if cell["chips"] > 1:
                    row["fault_no_exchange"] = compare.numbers(
                        reference(seed, first,
                                  rows=traffic["batch"] // cell["chips"]),
                        ref_obs)
                row["fault_state_unchanged"] = compare.numbers(
                    reference(seed, first, skip_update=True), ref_obs)
                row["control_s"] = time.perf_counter() - t2
            print(json.dumps(row), flush=True)
            out.write(json.dumps(row) + "\n")
            out.flush()
    return 0


if __name__ == "__main__":
    sys.exit(main())
