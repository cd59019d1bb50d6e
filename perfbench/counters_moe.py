"""The routed layers' counters, read from the program's registry
(``mxnet_tpu.telemetry.REGISTRY``) after the run: what
``gluon.contrib.moe.DroplessMoE`` reports from inside the step and
``parallel.TrainStep`` banks when the losses are fetched.  They count every
dispatch of the process, set-up's two included; readers use ratios, or
scale by the tokens the same counters saw."""


def _by_layer(name):
    from mxnet_tpu import telemetry
    return {dict(m.labels).get("layer"): float(m.value)
            for m in telemetry.REGISTRY.collect()
            if m.name == name and m.value}


def routed():
    """``{"pairs": {layer: pairs on held experts}, "tokens": tokens routed,
    summed over the layers, "max": {layer: the fullest held expert of any
    step}}``; None where the program keeps no such counters."""
    pairs = _by_layer("mxnet_moe_pairs_total")
    tokens = _by_layer("mxnet_moe_tokens_total")
    if not pairs or not tokens.get(None):
        return None
    return {"pairs": pairs, "tokens": tokens[None],
            "max": _by_layer("mxnet_moe_expert_tokens_max")}


def pairs_per_token():
    """Pairs on held experts per token and routed layer; None without the
    counters."""
    r = routed()
    return None if r is None else sum(r["pairs"].values()) / r["tokens"]
