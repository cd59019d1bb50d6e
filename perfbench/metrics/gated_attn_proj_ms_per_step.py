"""Device time a step spends in the region ``attn_proj``: the gated full-attention layer less the attention op: W_q (query and gate), W_k, W_v, the per-head q/k norms, rotary on the first dims, the output gate and W_o; the attention op itself (with the repeat of K and V to the query heads) counts under attention.
Self time of the trace's instructions whose ``op_name`` carries the
region's scopes (perfbench/scopes.py, perfbench/regions/<builder>.json),
fullest device, over the steps of the traced window.  Silent where the
program carries no region scope."""

from perfbench import scopes


def read(run):
    return scopes.region_ms_per_step(run, "attn_proj")
