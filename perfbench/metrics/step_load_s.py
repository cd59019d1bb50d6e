"""Seconds of set-up that the step program's build spent in JAX's
backend-compile stage: the compile cache's look-up, a real compile on a
miss, and loading the executable
(``mxnet_jit_build_seconds`` of the site ``parallel.TrainStep``, stage
``load``, as the program's own registry counted it).  Silent where the
program has no such counter."""

from perfbench import counters


def read(run):
    return counters.build_seconds("load")
