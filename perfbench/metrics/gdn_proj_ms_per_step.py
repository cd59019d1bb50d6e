"""Device time a step spends in the region ``gdn_proj``: a Gated DeltaNet mixer less its scan: W_qkvz and W_ba, the depthwise causal convolution with its SiLU, the l2 norms and the repeat of q and k to the value heads, decay and write strength, the gated RMSNorm and W_out.
Self time of the trace's instructions whose ``op_name`` carries the
region's scopes (perfbench/scopes.py, perfbench/regions/<builder>.json),
fullest device, over the steps of the traced window.  Silent where the
program carries no region scope."""

from perfbench import scopes


def read(run):
    return scopes.region_ms_per_step(run, "gdn_proj")
