"""Device time a step spends in all-reduce operations (the gradient
exchange of a data-parallel step): self time of the trace's ``all-reduce*``
events (start and done of an asynchronous one included), fullest device,
over the steps of the traced window.  Silent where the step ran none."""

from perfbench import scopes, trace_reduce


def is_all_reduce(name):
    return scopes.instruction_of(name).startswith("all-reduce")


def read(run):
    if run["trace"] is None:
        return None
    s = trace_reduce.seconds_of(run["trace"], is_all_reduce)
    return None if s is None else 1e3 * s / run["steps"]
