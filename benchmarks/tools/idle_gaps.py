"""The longest stretches of a kept trace in which the device ran no op,
each with the host spans (any name) that overlap it: what
``breakdown.idle_gaps`` sums up, one gap at a time.

    python3 benchmarks/tools/idle_gaps.py <file.xplane.pb> [top] [prefix ...]

No chip needed.
"""
import sys

from jax.profiler import ProfileData

from benchmarks import trace_reduce as tr


def gaps(path, top=10, prefixes=("serving/", "bench/", "io/", "jit/")):
    data = ProfileData.from_file(path)
    spans = tr.host_spans(data, prefixes)
    out = []
    for plane in data.planes:
        if not tr.DEVICE_PLANE.match(plane.name):
            continue
        line = tr._line(plane, tr.OPS_LINES) or tr._line(plane,
                                                         tr.MODULE_LINES)
        if line is None:
            continue
        busy = tr.union([(s, e) for s, e, _ in tr._events(line)])
        for (_, e0), (s1, _) in zip(busy, busy[1:]):
            out.append((s1 - e0, e0, s1, plane.name))
    out.sort(reverse=True)
    rows = []
    for dur, gs, ge, plane in out[:top]:
        over = [(name, max(gs, ss), min(ge, se)) for ss, se, name in spans
                if se > gs and ss < ge]
        rows.append({"plane": plane, "gap_ms": dur / 1e6,
                     "start_ns": gs,
                     "spans": [[name, (e - s) / 1e6]
                               for name, s, e in over]})
    return rows


if __name__ == "__main__":
    sys.path.insert(0, ".")
    top = int(sys.argv[2]) if len(sys.argv) > 2 else 10
    kw = {"prefixes": tuple(sys.argv[3:])} if len(sys.argv) > 3 else {}
    for row in gaps(sys.argv[1], top, **kw):
        print(f"{row['gap_ms']:10.3f} ms idle on {row['plane']} at "
              f"{row['start_ns']:.0f}: " + ", ".join(
                  f"{n} {ms:.3f}" for n, ms in row["spans"]))
