"""Seconds of set-up that the step program's build spent tracing the step
to a jaxpr: the package's own Python runs here, the model's forward and the
tape's backward
(``mxnet_jit_build_seconds`` of the site ``parallel.TrainStep``, stage
``trace``, as the program's own registry counted it).  Silent where the
program has no such counter."""

from perfbench import counters


def read(run):
    return counters.build_seconds("trace")
