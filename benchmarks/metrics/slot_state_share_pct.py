"""How far a decode step's cache traffic sits on the STATE's side: the
bytes of slot state a step reads and writes (every slot's, in and out:
the program's gauge ``serving_state_bytes_per_slot``) over those plus
the live keys and values it reads (the gauge
``serving_kv_bytes_per_token`` x the live positions of the window's
steps, from the client's stamps). Constant-size state against a cache
that grows with the sequence: the share falls as sessions lengthen."""
from benchmarks.metrics import _arch_decode, _parallel


def read(ctx):
    gauges = _parallel.cache_gauges(ctx)
    live = _arch_decode.live_positions_per_step(ctx, traced=False)
    if gauges is None or live is None:
        return None
    state = 2 * ctx["num_slots"] * gauges["state_bytes_per_slot"]
    return 100.0 * state / (state + live * gauges["kv_bytes_per_token"])
