"""The longest ready-to-ready interval between the window's dispatches over
their median (the first dispatch left out: set-up stands before it), from
the program's dispatch records (perfbench/counters_dispatch.py).  1.0 where
every dispatch takes the same time; a window in which the device waited on
the host once reads the length of that wait in dispatches.  Silent where
the program keeps no records, or the window has fewer than three
dispatches."""

import statistics

from perfbench import counters_dispatch


def read(run):
    records = counters_dispatch.window(run)
    if records is None or len(records) < 3:
        return None
    intervals = counters_dispatch.ready_intervals(records)
    return max(intervals) / statistics.median(intervals)
