"""Peak of the paged pool's blocks in use (sampled by the driver at
every token it is handed) over the pool's usable blocks."""


def read(ctx):
    if not ctx.get("pool_blocks"):
        return None
    return 100.0 * ctx["run"]["blocks_peak"] / (ctx["pool_blocks"] - 1)
