"""Device time of the attention kernel per step: the self time of the
Pallas calls in the trace (the flash kernel's are the step's only ones, and
carry no stable name yet, PERF.md section 7), on the fullest device, over
the steps of the traced window.  Silent where the step ran no such call."""

from perfbench import trace_reduce


def read(run):
    if run["trace"] is None:
        return None
    s = trace_reduce.seconds_of(run["trace"], trace_reduce.is_pallas_call)
    return None if s is None else 1e3 * s / run["steps"]
