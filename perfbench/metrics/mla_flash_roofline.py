"""The least time the chip could take for the causal attention of one step
with 192-wide queries and keys and 128-wide values
(perfbench/counts_mla_moe.py: the larger of FLOPs over the bf16 peak and
bytes over the HBM peak, per layer, times the layers), over the time the
flash kernels took: the Pallas calls named ``flash_*``, not every
``tpu_custom_call`` (the compiler's grouped products are such calls too),
fullest device, over the traced window.  Silent where no such kernel ran."""

from perfbench import counts, counts_mla_moe, trace_reduce


def is_flash_kernel(name):
    return name.startswith("flash_") and trace_reduce.is_pallas_call(name)


def read(run):
    if run["trace"] is None:
        return None
    took = trace_reduce.seconds_of(run["trace"], is_flash_kernel)
    if took is None:
        return None
    cfg, traffic = run["cell"]["config"], run["cell"]["traffic"]
    shape = (cfg, traffic["batch"] // run["cell"]["chips"], traffic["seq"])
    least, _bound = counts.roofline_seconds(
        counts_mla_moe.attention_flops_per_layer(*shape),
        counts_mla_moe.attention_bytes_per_layer(*shape), run["peak"])
    return 100.0 * cfg["num_hidden_layers"] * run["steps"] * least / took
