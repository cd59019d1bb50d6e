"""Device time a step spends in the region ``attn_proj`` of a looped model: the mixer of every layer application less the attention op: W_q, W_k, W_v, W_o, the mixer's two norms (before the projections and after W_o), RoPE, the head split and merge; the attention op itself counts under attention.  The applications made again in the backward count where their forward does.
Self time of the trace's instructions whose ``op_name`` carries the
region's scopes (perfbench/scopes.py, perfbench/regions/<builder>.json),
fullest device, over the steps of the traced window.  Silent where the
program carries no region scope."""

from perfbench import scopes


def read(run):
    return scopes.region_ms_per_step(run, "attn_proj")
