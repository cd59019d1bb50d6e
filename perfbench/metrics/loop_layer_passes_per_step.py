"""Forward passes of a decoder layer in one step of a looped model, the
first and those made again in the backward, from the program's counter
``mxnet_loop_layer_passes_total{model, kind}`` (perfbench/counters_loop.py)
and the loop's applications a step (perfbench/counts_loop_lm.py: layers
times loop steps): 24 where every application keeps its activations, 48
where all are made again.  Silent where the program keeps no such
counter."""

from perfbench import counters_loop, counts_loop_lm


def read(run):
    return counters_loop.layer_passes_per_step(
        counts_loop_lm.layer_applications(run["cell"]["config"]))
