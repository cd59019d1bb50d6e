"""Seconds of set-up inside ``TrainStep._resolve``: fixing the parameter
and state order, creating the optimizer's state and, where the step is
resolved from a batch, the imperative forward that finishes deferred init
(one small program an op) (``mxnet_trainstep_resolve_seconds``, as the
program's own registry counted it).  Silent where the program has no such
counter."""

from perfbench import counters_dispatch


def read(run):
    return counters_dispatch.registry_value("mxnet_trainstep_resolve_seconds")
