"""Device time a step spends in the region ``optimizer``: what of the fused
Adam update the compiler left in fusions of its own.  The part it fused onto
a weight-gradient matmul counts with the matmul's region
(perfbench/scopes.py, rule 2), so this is a floor on the update's cost.
Self time of the trace's instructions whose ``op_name`` carries the
region's scopes (perfbench/scopes.py, perfbench/regions/<builder>.json),
fullest device, over the steps of the traced window.  Silent where the
program carries no region scope."""

from perfbench import scopes


def read(run):
    return scopes.region_ms_per_step(run, "optimizer")
