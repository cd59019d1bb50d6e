"""Seconds of set-up that the step program's build spent lowering the
step's jaxpr to an MLIR module
(``mxnet_jit_build_seconds`` of the site ``parallel.TrainStep``, stage
``lower``, as the program's own registry counted it).  Silent where the
program has no such counter."""

from perfbench import counters


def read(run):
    return counters.build_seconds("lower")
