"""Device time a step spends in the region ``short_conv``: a gated short-convolution operator less the op ``contrib.gated_short_conv``: W_in, W_out and what surrounds them, forward, made again and backward.
Self time of the trace's instructions whose ``op_name`` carries the
region's scopes (perfbench/scopes.py, perfbench/regions/<builder>.json),
fullest device, over the steps of the traced window.  Silent where the
program carries no region scope."""

from perfbench import scopes


def read(run):
    return scopes.region_ms_per_step(run, "short_conv")
