"""Share of the traced window in which no operation ran on the device:
1 - union of the device's op intervals / window. Serves ``.gap``,
``.tput`` and ``.train``."""


def read(ctx):
    red = ctx["trace"]
    if red is None or not red["window_s"]:
        return None
    return 100.0 * (1.0 - red["busy_s"] / red["window_s"])
