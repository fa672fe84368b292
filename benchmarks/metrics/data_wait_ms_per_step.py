"""The benchmark's span around ``next(loader)``, per step."""


def read(ctx):
    a, b = ctx["spans"]["before"], ctx["spans"]["after"]
    sec = b["seconds"].get("data_wait", 0.0) - a["seconds"].get(
        "data_wait", 0.0)
    return 1e3 * sec / ctx["steps"] if ctx["steps"] else None
