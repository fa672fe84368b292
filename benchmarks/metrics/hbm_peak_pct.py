"""Peak device memory in use over the chip's HBM. Serves ``.train``."""


def read(ctx):
    if not ctx.get("peaks") or not ctx.get("memory_peak_bytes"):
        return None
    return 100.0 * ctx["memory_peak_bytes"] / ctx["peaks"]["hbm_bytes"]
