"""The least time the chip could take for the causal attention of the
MODEL's layer applications of one step at ``head_dim``-wide queries, keys
and values (perfbench/counts_loop_lm.py: the larger of ``3 B H S^2 (d + d)``
FLOPs over the bf16 peak and bytes over the HBM peak, per application,
times the loop's applications: each once forward and once backward), over
the time the flash kernels took: the Pallas calls named ``flash_*``,
fullest device, over the traced window.  A forward call that the program
makes again to save memory adds to the time and not to the work, so it
lowers the share.  Silent where no such kernel ran."""

from perfbench import counts, counts_loop_lm, trace_reduce
from perfbench.metrics.mla_flash_roofline import is_flash_kernel


def read(run):
    if run["trace"] is None:
        return None
    took = trace_reduce.seconds_of(run["trace"], is_flash_kernel)
    if not took:
        return None
    cfg, traffic = run["cell"]["config"], run["cell"]["traffic"]
    shape = (cfg, traffic["batch"] // run["cell"]["chips"], traffic["seq"])
    least, _bound = counts.roofline_seconds(
        counts_loop_lm.attention_flops_per_layer(*shape),
        counts_loop_lm.attention_bytes_per_layer(*shape), run["peak"])
    return 100.0 * counts_loop_lm.layer_applications(cfg) * run["steps"] \
        * least / took
