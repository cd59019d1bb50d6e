"""How near the host is to setting the pace: 100 x (1 - the seconds it
waited inside the fetches of the window's losses over the seconds from the
window's first dispatch beginning to its last losses being ready), from
the program's dispatch records (perfbench/counters_dispatch.py).  What is
not waiting is the host's own work: the dispatches' phases, the feed, the
runner.  Silent where the program keeps no records."""

from perfbench import counters_dispatch


def read(run):
    records = counters_dispatch.window(run)
    if records is None:
        return None
    span = records[-1].t_ready - records[0].t_begin
    waited = sum(r.fetch_wait_s for r in records)
    return 100.0 * (1.0 - waited / span)
