"""Milliseconds of its own that the host spends in one dispatch, the mean
over the window's dispatches: the four phases of ``TrainStep._dispatch``
(bookkeeping, h2d, enqueue, writeback) as the program's dispatch records
hold them (perfbench/counters_dispatch.py).  Hidden behind the device's
time while the device is fed.  Silent where the program keeps no
records."""

from perfbench import counters_dispatch


def read(run):
    records = counters_dispatch.window(run)
    if records is None:
        return None
    return 1e3 * sum(map(counters_dispatch.host_seconds, records)) \
        / len(records)
