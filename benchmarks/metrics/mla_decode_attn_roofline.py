"""The absorbed latent-attention kernel against its roofline: for the
live positions of the traced steps, the larger of its operations over
the peak and its cache bytes over the bandwidth, in every layer, over
the kernel's own device time a step."""
from benchmarks.metrics import _arch_decode


def read(ctx):
    ms = _arch_decode.kernel_ms_per_step(ctx, "mla_decode_attn")
    live = _arch_decode.live_positions_per_step(ctx, traced=True)
    if ms is None or live is None:
        return None
    ops, nbytes = ctx["flops"].mla_decode_attn_cost(
        ctx["model"], live, ctx["kv_bytes_per_value"])
    layers = ctx["model"]["num_hidden_layers"]
    return _arch_decode.roofline_pct(ctx, layers * ops, layers * nbytes,
                                     ms)
