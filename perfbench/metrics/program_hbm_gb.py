"""What the dispatched step program plans to hold on its fullest device:
arguments + temporaries + outputs - aliased, from the loaded executable's
own memory statistics (``memory_stats`` leaves the temporaries out on this
runtime)."""


def read(run):
    return run["program"]["planned_bytes"] / 1e9
