"""The least time the chip could take for the Mamba-2 scan of one step's
state-space layers (perfbench/counts_ssm_hybrid.py: the larger of the
chunked form's FLOPs over the bf16 peak and its operands' and results'
bytes over the HBM peak, per layer, times the Mamba-2 layers), over the
time the region ``ssd_scan`` took: BY REGION, whatever implements the scan,
so a later kernel is read against the same work.  Fullest device, over the
traced window.  Silent where the program carries no such region."""

from perfbench import counts, counts_ssm_hybrid, scopes


def read(run):
    took_ms = scopes.region_ms_per_step(run, "ssd_scan")
    if not took_ms:
        return None
    cfg, traffic = run["cell"]["config"], run["cell"]["traffic"]
    shape = (cfg, traffic["batch"] // run["cell"]["chips"], traffic["seq"])
    least, _bound = counts.roofline_seconds(
        counts_ssm_hybrid.scan_flops_per_layer(*shape),
        counts_ssm_hybrid.scan_bytes_per_layer(*shape), run["peak"])
    mamba, _full = counts_ssm_hybrid.layer_kinds(cfg)
    return 100.0 * mamba * least / (took_ms / 1e3)
