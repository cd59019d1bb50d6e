"""Dispatches of the window that found the device with nothing queued: no
earlier dispatch of the step was still running when they had been enqueued
(``fed`` false in the program's dispatch records,
perfbench/counters_dispatch.py).  1 in a sound window, the dispatch that
opens it (set-up fetched the one before); more means the host fell behind.
Silent where the program keeps no records."""

from perfbench import counters_dispatch


def read(run):
    records = counters_dispatch.window(run)
    if records is None:
        return None
    return sum(1 for r in records if not r.fed)
