"""Counters the program keeps about itself, read from its registry
(``mxnet_tpu.telemetry.REGISTRY``) after the run."""


def build_seconds(stage, site="parallel.TrainStep"):
    """``mxnet_jit_build_seconds{site=,stage=}``: seconds the site's
    dispatches spent in one stage of building their program (trace, lower,
    load); None where the program keeps no such counter."""
    from mxnet_tpu import telemetry
    counter = telemetry.REGISTRY.get("mxnet_jit_build_seconds",
                                     labels={"site": site, "stage": stage})
    return None if counter is None else float(counter.value)
