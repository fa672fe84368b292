"""Host work of one engine step: the program's own span
``serving/step`` minus ``serving/sync`` (waiting for the device), over
the window, per decode step. Serves ``.gap`` and ``.tput``."""


def read(ctx):
    a, b = ctx["run"]["before"], ctx["run"]["after"]
    steps = b["decode_steps"] - a["decode_steps"]
    if steps <= 0 or "serving/step" not in b["span_s"]:
        return None

    def delta(name):
        return b["span_s"].get(name, 0.0) - a["span_s"].get(name, 0.0)
    return 1e3 * (delta("serving/step") - delta("serving/sync")) / steps
