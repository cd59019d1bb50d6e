"""Read, on the chip and at the cell's own size, what faults of the
short-convolution + attention MoE model read in the comparison that decides
``correct``: the plain reference with one thing broken, put in the
program's place, against the sound reference (a run of the cell reads the
program itself, seed by seed).  The faults:

    conv_one_tap            the convolution without its shift: the current
                            position's tap alone
    gate_left_out           ``C *`` left out of the convolution's output
    bias_in_weights         the expert bias in the chosen weights as well
                            as in the choice
    unnormalised            the top-4 weights not divided by their sum
    qk_norms_left_out       q and k not normed per head
    rope_theta_1e4          RoPE at the base 1e4, not the published 1e6
    half_positions          the second half of every row's positions left
                            out, the mean taken over the first
    state_unchanged         the optimizer's update left out
    control_fp8             every matrix product with operands, results and
                            cotangents rounded to 3 mantissa bits

One process for all of them, since the sound reference is made once.  Not
part of a benchmark run.

    python3 -m perfbench.tools.faults_conv_moe --workload <cell> \\
        --seed 1 --out faults_<cell>.jsonl
"""

import contextlib
import sys
import types

from perfbench.tools import faults_ssm_hybrid


def _patches(ref, name):
    import jax.numpy as jnp
    return {
        "conv_one_tap": {"_conv": lambda v, w: v * w[:, -1]},
        "gate_left_out": {"_gate": lambda c, z: z},
        "bias_in_weights": {"_weights_of": lambda s, bias, chosen:
                            jnp.take_along_axis(s + bias, chosen, axis=1)},
        "qk_norms_left_out": {"_qk_norm": lambda x, w, eps: x},
    }.get(name, {})


def _config_faults(cfg):
    """The faults that change a key of the configuration instead of a
    function of the reference (``arguments`` has those that are arguments
    of ``train_steps``)."""
    return {"unnormalised": {"norm_topk_prob": False},
            "rope_theta_1e4": {"rope_parameters": dict(
                cfg["rope_parameters"], rope_theta=10000)}}


FAULTS = ("conv_one_tap", "gate_left_out", "bias_in_weights",
          "unnormalised", "qk_norms_left_out", "rope_theta_1e4",
          "half_positions", "state_unchanged", "control_fp8")


@contextlib.contextmanager
def planted(ref, name, cfg):
    """The reference module with the fault ``name`` in it; yields the keys
    to change in the configuration (every fault changes one, so that the
    reference's jitted programs are traced again)."""
    patches = _patches(ref, name)
    change = dict(_config_faults(cfg).get(name, {}),
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
    return {"half_positions": {"positions": traffic["seq"] // 2},
            "state_unchanged": {"skip_update": True},
            "control_fp8": {"matmul": ref.FP8_MATMUL}}.get(name, {})


# faults_ssm_hybrid's driver, run with this file's faults: the faults tools
# differ in FAULTS, planted and arguments alone (PERF.md section 7)
main = types.FunctionType(
    faults_ssm_hybrid.main.__code__,
    dict(vars(faults_ssm_hybrid), __doc__=__doc__, FAULTS=FAULTS,
         planted=planted, arguments=arguments), "main",
    faults_ssm_hybrid.main.__defaults__)


if __name__ == "__main__":
    sys.exit(main())
