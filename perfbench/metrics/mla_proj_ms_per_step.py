"""Device time a step spends in the region ``mla_proj``: the latent attention's projections (W_q, W_kva with its norm, W_kvb, W_o), the rotary turn and the assembly of keys; the attention op itself counts under attention.
Self time of the trace's instructions whose ``op_name`` carries the
region's scopes (perfbench/scopes.py, perfbench/regions/<builder>.json),
fullest device, over the steps of the traced window.  Silent where the
program carries no region scope."""

from perfbench import scopes


def read(run):
    return scopes.region_ms_per_step(run, "mla_proj")
