"""Device time a step spends in the region ``head_loss``: the vocabulary
projection (the model's ``decoder`` block) and the loss, forward and
backward, the decoder weight's gradient matmul with the Adam update the
compiler fused onto it included.
Self time of the trace's instructions whose ``op_name`` carries the
region's scopes (perfbench/scopes.py, perfbench/regions/<builder>.json),
fullest device, over the steps of the traced window.  Silent where the
program carries no region scope."""

from perfbench import scopes


def read(run):
    return scopes.region_ms_per_step(run, "head_loss")
