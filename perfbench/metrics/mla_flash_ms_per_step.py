"""Device time of the flash attention kernels per step where they are not
the step's only Pallas calls: the self time of the calls NAMED ``flash_*``
(``mla_flash_roofline``'s filter; ``flash_ms_per_step`` goes by the
custom-call target and would count the compiler's grouped products too), on
the fullest device, over the steps of the traced window.  Silent where no
such kernel ran."""

from perfbench import trace_reduce
from perfbench.metrics.mla_flash_roofline import is_flash_kernel


def read(run):
    if run["trace"] is None:
        return None
    s = trace_reduce.seconds_of(run["trace"], is_flash_kernel)
    return None if s is None else 1e3 * s / run["steps"]
