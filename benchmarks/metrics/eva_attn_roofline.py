"""The decode-attention kernel against its memory bound where a cache
entry is not a position: the keys and values of the ENTRIES the traced
steps read (summaries of finished windows + positions of the current
one, from the client's stamps: ``flops_<arch>.entries``), in every layer,
at the chip's HBM bandwidth, over the kernel's own device time a step.
The kernel copies a slot's last block whole; the part of it past the
slot's entries is not in the numerator."""
from benchmarks.metrics import _arch_decode, _eva


def read(ctx):
    ms = _arch_decode.kernel_ms_per_step(ctx, "eva_attn")
    live = _eva.live_entries_per_step(ctx, traced=True)
    if ms is None or live is None:
        return None
    ops, nbytes = ctx["flops"].attn_decode_cost(
        ctx["model"], live, ctx["kv_bytes_per_value"])
    layers = ctx["model"]["num_hidden_layers"]
    return _arch_decode.roofline_pct(ctx, layers * ops, layers * nbytes,
                                     ms)
