"""Forward passes of the gated short convolution in one step, the first
and those made again in the backward, from the program's counter
``mxnet_short_conv_passes_total{layer, kind}`` (grown where a step is
traced: one pass for a layer whose activations are kept, two for a layer
made again) and the convolution layers a step runs
(perfbench/counts_conv_moe.py): 4 where every layer keeps its activations,
8 where all are made again.  The counter grows once a TRACE of the step,
and a process may trace it more than once: the traces are the layers it
counted (a kept pass is one, two passes made again are one) over the
convolution layers.  Silent where the program keeps no such counter."""

from perfbench import counts_conv_moe


def read(run):
    from mxnet_tpu import telemetry
    passes = {}
    for m in telemetry.REGISTRY.collect():
        if m.name == "mxnet_short_conv_passes_total" and m.value:
            kind = dict(m.labels).get("kind")
            passes[kind] = passes.get(kind, 0.0) + float(m.value)
    if not passes:
        return None
    counted = passes.get("kept", 0.0) + passes.get("made_again", 0.0) / 2
    layers = counts_conv_moe.conv_layers(run["cell"]["config"])
    return layers * sum(passes.values()) / counted
