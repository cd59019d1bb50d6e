"""The fullest held expert of any step over the mean load of a held expert
and step, in the layer where that is largest
(``mxnet_moe_expert_tokens_max{layer}`` over ``mxnet_moe_pairs_total
{layer}`` / steps / held experts; the steps from ``mxnet_moe_tokens_total``).
The grouped products wait for their fullest group.  Silent where the program
keeps no such counters."""

from perfbench import counters_moe


def read(run):
    r = counters_moe.routed()
    if r is None or not r["max"]:
        return None
    cfg, traffic = run["cell"]["config"], run["cell"]["traffic"]
    steps = r["tokens"] / len(r["pairs"]) / (traffic["batch"] * traffic["seq"])
    held = cfg["experts_held"][1]
    return max(r["max"][layer] / (pairs / steps / held)
               for layer, pairs in r["pairs"].items() if pairs)
