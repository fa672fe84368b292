"""Share of the decode batch that did useful work: tokens that decode
steps produced over decode_steps x num_slots (program counters; every
prefill yields its request's first token, the rest are decode's)."""


def read(ctx):
    a, b = ctx["run"]["before"], ctx["run"]["after"]
    steps = b["decode_steps"] - a["decode_steps"]
    if steps <= 0:
        return None
    tokens = (b["tokens_generated"] - a["tokens_generated"]) \
        - (b["prefills"] - a["prefills"])
    return 100.0 * tokens / (steps * ctx["num_slots"])
