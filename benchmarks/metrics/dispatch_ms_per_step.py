"""The benchmark's span around the call to the compiled step: the
enqueue, not the completion. Serves ``.train``."""


def read(ctx):
    a, b = ctx["spans"]["before"], ctx["spans"]["after"]
    sec = b["seconds"].get("dispatch", 0.0) - a["seconds"].get(
        "dispatch", 0.0)
    return 1e3 * sec / ctx["steps"] if ctx["steps"] else None
