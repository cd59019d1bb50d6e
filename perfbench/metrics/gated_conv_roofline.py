"""The least time the chip could take for the gated short convolutions of
one step (perfbench/counts_conv_moe.py: the larger of ``C * conv(B * x)``'s
FLOPs over the bf16 peak and its operands' and results' bytes over the HBM
peak, forward and backward, per layer, times the convolution layers), over
the time the region ``gated_conv`` took: BY REGION, whatever implements
the op (it runs under the scope ``gated_short_conv``), so a later kernel is
read against the same work.  The region's time includes the forward the
program makes again in the backward; the work does not.  Fullest device,
over the traced window.  Silent where the program carries no such
region."""

from perfbench import counts, counts_conv_moe, scopes


def read(run):
    took_ms = scopes.region_ms_per_step(run, "gated_conv")
    if not took_ms:
        return None
    cfg, traffic = run["cell"]["config"], run["cell"]["traffic"]
    tokens = traffic["batch"] * traffic["seq"] // run["cell"]["chips"]
    least, _bound = counts.roofline_seconds(
        counts_conv_moe.gated_conv_flops(cfg, tokens),
        counts_conv_moe.gated_conv_bytes(cfg, tokens), run["peak"])
    return 100.0 * counts_conv_moe.conv_layers(cfg) * least / (took_ms / 1e3)
