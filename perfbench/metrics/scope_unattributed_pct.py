"""Share of the device's busy time in instructions that no region rule
names (perfbench/scopes.py): the scan's own slicing and carry copies, and
whatever a later change forgets to put under a scope.  Silent where the
program carries no region scope at all."""

from perfbench import scopes


def read(run):
    s = scopes.split(run)
    if s is None:
        return None
    return 100.0 * s["by_region"].get(scopes.UNATTRIBUTED, 0.0) / s["busy_s"]
