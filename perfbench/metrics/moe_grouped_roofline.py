"""The least time the chip could take for the grouped matrix products of the
routed experts, over the time its grouped-product kernels took (the
compiler's ``ragged-dot*`` custom calls, fullest device, traced window).
Work from perfbench/counts_mla_moe.py at the pairs the run really routed:
pairs per token and layer from the program's counters
(``mxnet_moe_pairs_total`` over ``mxnet_moe_tokens_total``) times the
window's tokens, a layer at a time (every layer reads its own experts).
The kernels' time includes the forward the program runs again in its
backward; the work does not.  Silent without the kernels or the counters."""

from perfbench import counters_moe, counts, counts_mla_moe, trace_reduce


def is_grouped_product(name):
    return name.startswith("ragged-dot") and trace_reduce.is_pallas_call(name)


def read(run):
    if run["trace"] is None:
        return None
    took = trace_reduce.seconds_of(run["trace"], is_grouped_product)
    per_token = counters_moe.pairs_per_token()
    if took is None or per_token is None:
        return None
    cfg, traffic = run["cell"]["config"], run["cell"]["traffic"]
    pairs = per_token * traffic["batch"] * traffic["seq"] \
        / run["cell"]["chips"]                  # of one layer and step
    least, _bound = counts.roofline_seconds(
        counts_mla_moe.grouped_flops(cfg, pairs),
        counts_mla_moe.grouped_bytes(cfg, pairs), run["peak"])
    layers = cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]
    return 100.0 * layers * run["steps"] * least / took
