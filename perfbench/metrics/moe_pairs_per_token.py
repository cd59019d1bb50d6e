"""(token, expert) pairs that fell on the experts this chip holds, per token
and routed layer, from the program's counters (``mxnet_moe_pairs_total``
over ``mxnet_moe_tokens_total``, every dispatch of the process).  A router
that spreads evenly gives k * held / experts.  Silent where the program
keeps no such counters."""

from perfbench import counters_moe


def read(_run):
    return counters_moe.pairs_per_token()
