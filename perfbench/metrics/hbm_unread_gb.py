"""What sat on the step's fullest device just before its first dispatch and
is no argument of the step program: ``mxnet_trainstep_device_bytes``
``in_use`` less ``arguments``, as the program read them where it built the
step (``memory_stats`` and the argument shards' sizes).  The parameters'
gradient buffers, which ``TrainStep`` never reads, are most of it; the
program's temporaries are not in it (they are reserved at the call).
Silent where the program keeps no such gauge (a runtime without
``memory_stats``)."""

from perfbench import counters_dispatch


def read(run):
    name = "mxnet_trainstep_device_bytes"
    in_use = counters_dispatch.registry_value(name, {"kind": "in_use"})
    arguments = counters_dispatch.registry_value(name, {"kind": "arguments"})
    if in_use is None or arguments is None:
        return None
    return (in_use - arguments) / 1e9
