"""Share of the prefill programs' tokens that is padding: 1 - prompt
tokens / ``Request.prefill_tokens_dispatched`` (the bucket the engine
padded each prompt to) over the requests admitted inside the window."""


def read(ctx):
    lo, hi = ctx["run"]["t_open"], ctx["run"]["t_close"]
    prompt = padded = 0
    for r in ctx["run"]["recs"]:
        req = r.req
        if req is None or req.t_admitted is None \
                or not lo <= req.t_admitted < hi:
            continue
        n = getattr(req, "prefill_tokens_dispatched", None)
        if not n:
            return None     # a program that does not record it
        prompt += len(r.spec["prompt"])
        padded += n
    return 100.0 * (1.0 - prompt / padded) if padded else None
