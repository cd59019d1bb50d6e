"""The program's own record of its dispatches, read after the run:
``mxnet_tpu.telemetry.stepclock.DISPATCHES``, a ring of one record per
``TrainStep`` dispatch (host phases, was the device fed, the wait in the
fetch of its losses; always on), and the registry's ``mxnet_trainstep_*``
counters that the same records bank.  Silent where the program keeps no
such ring or counter."""


def window(run):
    """The records of the window's dispatches: the last ``steps /
    scan_steps`` of the ring (set-up's two dispatches stand before them,
    and the reference that runs after the window uses no ``TrainStep``),
    every one with its fetch stamped.  None where the program keeps no
    ring, or the ring no longer holds the whole window."""
    from mxnet_tpu.telemetry import stepclock
    ring = getattr(stepclock, "DISPATCHES", None)
    dispatches = run["steps"] // run["cell"]["traffic"]["scan_steps"]
    if ring is None or not 0 < dispatches <= len(ring):
        return None
    records = list(ring)[-dispatches:]
    if any(r.t_ready is None for r in records):
        return None
    return records


def host_seconds(record):
    """The host's own seconds in one dispatch: its four phases."""
    return (record.bookkeeping_s + record.h2d_s + record.enqueue_s
            + record.writeback_s)


def ready_intervals(records):
    """Seconds from one dispatch's losses being ready to the next one's,
    the window's first dispatch left out (set-up stands before it)."""
    return [b.t_ready - a.t_ready for a, b in zip(records, records[1:])]


def registry_value(name, labels=None):
    """A counter or gauge of the program's registry; None where it keeps
    none under that name and labels."""
    from mxnet_tpu import telemetry
    metric = telemetry.REGISTRY.get(name, labels=labels)
    return None if metric is None else float(metric.value)
