"""Device time a step spends in the region ``ssm_proj``: a Mamba-2 mixer less its scan: W_in, the depthwise causal convolution with its bias and SiLU, the step's softplus, the split into x, B and C, the gated norm and W_out.
Self time of the trace's instructions whose ``op_name`` carries the
region's scopes (perfbench/scopes.py, perfbench/regions/<builder>.json),
fullest device, over the steps of the traced window.  Silent where the
program carries no region scope."""

from perfbench import scopes


def read(run):
    return scopes.region_ms_per_step(run, "ssm_proj")
