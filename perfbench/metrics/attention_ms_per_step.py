"""Device time a step spends in the region ``attention``: the head split,
whichever of the Pallas flash kernel and the dense softmax(QK^T)V the shapes
and the platform picked, and the head merge, forward and backward.
Self time of the trace's instructions whose ``op_name`` carries the
region's scopes (perfbench/scopes.py, perfbench/regions/<builder>.json),
fullest device, over the steps of the traced window.  Silent where the
program carries no region scope."""

from perfbench import scopes


def read(run):
    return scopes.region_ms_per_step(run, "attention")
