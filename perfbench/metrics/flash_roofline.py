"""The least time the chip could take for the attention of one step, over
the time its kernel took: the larger of FLOPs over the bf16 peak and bytes
over the HBM peak (compute bounds it at these shapes: 12*B*H*S^2*D FLOPs
against 12*B*H*S*D*2 bytes is S/2 FLOPs a byte, over the chip's ridge of
240 from seq 512 up), per layer, times the layers, for one device's rows."""

from perfbench import counts, trace_reduce


def read(run):
    if run["trace"] is None:
        return None
    took = trace_reduce.seconds_of(run["trace"], trace_reduce.is_pallas_call)
    if took is None:
        return None
    cfg, traffic = run["cell"]["config"], run["cell"]["traffic"]
    heads = cfg["num_attention_heads"]
    shape = (traffic["batch"] // run["cell"]["chips"], heads,
             traffic["seq"], cfg["hidden_size"] // heads)
    least, _bound = counts.roofline_seconds(
        counts.attention_flops_per_layer(*shape),
        counts.attention_bytes_per_layer(*shape), run["peak"])
    return 100.0 * cfg["num_hidden_layers"] * run["steps"] * least / took
