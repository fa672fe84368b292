"""Look at one trace by hand: planes, lines, the heaviest events of each
line, and the stats one event carries.

    python3 benchmarks/tools/trace_summary.py <file.xplane.pb> [name-part]
"""
import sys

from jax.profiler import ProfileData


def main(path, needle=None):
    data = ProfileData.from_file(path)
    for plane in data.planes:
        lines = list(plane.lines)
        print(f"PLANE {plane.name!r}: {len(lines)} lines")
        for line in lines:
            by = {}
            n, first, last, shown = 0, None, None, 0
            for e in line.events:
                n += 1
                rec = by.setdefault(e.name, [0, 0.0])
                rec[0] += 1
                rec[1] += e.duration_ns
                first = e.start_ns if first is None else min(first,
                                                             e.start_ns)
                last = max(last or 0, e.start_ns + e.duration_ns)
                if needle and needle in e.name and shown < 2:
                    shown += 1
                    print(f"      STATS of {e.name!r}: "
                          f"{[(k, str(v)[:300]) for k, v in e.stats]}")
            if not n:
                continue
            print(f"  LINE {line.name!r}: {n} events, "
                  f"{(last - first) / 1e9:.3f} s span, starts {first}")
            top = sorted(by.items(), key=lambda kv: -kv[1][1])[:12]
            for name, (calls, ns) in top:
                print(f"      {ns / 1e6:10.3f} ms {calls:7d} x {name[:110]}")


if __name__ == "__main__":
    main(sys.argv[1], sys.argv[2] if len(sys.argv) > 2 else None)
