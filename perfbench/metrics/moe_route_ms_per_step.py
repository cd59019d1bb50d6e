"""Device time a step spends in the region ``moe_route``: the expert layers' router (float32 scores, top-k, weights) and the moves of rows around the grouped products: sort by expert, gather, gather back with the weights.
Self time of the trace's instructions whose ``op_name`` carries the
region's scopes (perfbench/scopes.py, perfbench/regions/<builder>.json),
fullest device, over the steps of the traced window.  Silent where the
program carries no region scope."""

from perfbench import scopes


def read(run):
    return scopes.region_ms_per_step(run, "moe_route")
