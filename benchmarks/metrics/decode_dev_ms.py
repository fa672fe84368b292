"""Median device time of one execution of the decode program."""
from benchmarks import trace_reduce


def read(ctx):
    if ctx["trace"] is None:
        return None
    med = trace_reduce.median_execution_s(ctx["trace"],
                                          ctx["programs"]["decode"])
    return None if med is None else 1e3 * med
