"""The least time the chip could take for the causal attention of one step's
full-attention layers at ``head_dim``-wide queries, keys and values
(perfbench/counts_gdn_moe.py: the larger of ``3 B H S^2 (d + d)`` FLOPs over
the bf16 peak and bytes over the HBM peak, per layer, times the full
layers), over the time the flash kernels took: the Pallas calls named
``flash_*``, fullest device, over the traced window.  Silent where no such
kernel ran."""

from perfbench import counts, counts_gdn_moe, trace_reduce
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
        counts_gdn_moe.attention_flops_per_layer(*shape),
        counts_gdn_moe.attention_bytes_per_layer(*shape), run["peak"])
    _linear, full = counts_gdn_moe.layer_kinds(cfg)
    return 100.0 * full * run["steps"] * least / took
