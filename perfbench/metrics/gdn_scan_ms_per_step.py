"""Device time a step spends in the region ``gdn_scan``: the gated delta rule of the linear-attention layers, forward and backward, whatever implements it (the op ``contrib.gated_delta_rule`` runs under the scope of that name).
Self time of the trace's instructions whose ``op_name`` carries the
region's scopes (perfbench/scopes.py, perfbench/regions/<builder>.json),
fullest device, over the steps of the traced window.  Silent where the
program carries no region scope."""

from perfbench import scopes


def read(run):
    return scopes.region_ms_per_step(run, "gdn_scan")
