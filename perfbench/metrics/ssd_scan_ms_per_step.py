"""Device time a step spends in the region ``ssd_scan``: the Mamba-2 scan of the state-space layers, forward, made again and backward, whatever implements it (the op ``contrib.ssd_scan`` runs under the scope of that name).
Self time of the trace's instructions whose ``op_name`` carries the
region's scopes (perfbench/scopes.py, perfbench/regions/<builder>.json),
fullest device, over the steps of the traced window.  Silent where the
program carries no region scope."""

from perfbench import scopes


def read(run):
    return scopes.region_ms_per_step(run, "ssd_scan")
