"""Device time a step spends in the region ``encoder_dense``: everything
under an encoder layer that is not attention (the four projections, GELU,
residuals, both LayerNorms), forward and backward.
Self time of the trace's instructions whose ``op_name`` carries the
region's scopes (perfbench/scopes.py, perfbench/regions/<builder>.json),
fullest device, over the steps of the traced window.  Silent where the
program carries no region scope."""

from perfbench import scopes


def read(run):
    return scopes.region_ms_per_step(run, "encoder_dense")
