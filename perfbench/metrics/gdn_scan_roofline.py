"""The least time the chip could take for the gated delta rule of one step's
linear-attention layers (perfbench/counts_gdn_moe.py: the larger of the
chunked form's FLOPs over the bf16 peak and its operands' and results'
bytes over the HBM peak, per layer, times the linear layers), over the time
the region ``gdn_scan`` took: BY REGION, whatever implements the scan, so a
later kernel is read against the same work.  Fullest device, over the
traced window.  Silent where the program carries no such region."""

from perfbench import counts, counts_gdn_moe, scopes


def read(run):
    took_ms = scopes.region_ms_per_step(run, "gdn_scan")
    if not took_ms:
        return None
    cfg, traffic = run["cell"]["config"], run["cell"]["traffic"]
    shape = (cfg, traffic["batch"] // run["cell"]["chips"], traffic["seq"])
    least, _bound = counts.roofline_seconds(
        counts_gdn_moe.scan_flops_per_layer(*shape),
        counts_gdn_moe.scan_bytes_per_layer(*shape), run["peak"])
    linear, _full = counts_gdn_moe.layer_kinds(cfg)
    return 100.0 * linear * least / (took_ms / 1e3)
