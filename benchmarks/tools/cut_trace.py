"""Cut a short interval out of a recorded trace, to keep with the tests.

    python3 benchmarks/tools/cut_trace.py <in.xplane.pb> <out.xplane.pb> \
        <from_ms> <to_ms> [host-span-prefix ...]

Keeps, of every device plane, the lines the reduction reads (events
that start inside the interval) and, of the host planes, only the
annotation scopes with the given prefixes. Times are kept as recorded.
No chip needed.
"""
import sys

from jax.profiler import ProfileData

KEEP_DEVICE_LINES = ("XLA Modules", "XLA Ops")


def _q(s):
    return '"' + s.replace("\\", "\\\\").replace('"', '\\"') + '"'


def cut(src, dst, lo_ns, hi_ns, prefixes):
    data = ProfileData.from_file(src)
    out = []
    for pid, plane in enumerate(data.planes):
        device = plane.name.startswith("/device:TPU:")
        meta, lines = {}, []
        for lid, line in enumerate(plane.lines):
            if device and line.name not in KEEP_DEVICE_LINES:
                continue
            evs = []
            for e in line.events:
                if not lo_ns <= e.start_ns < hi_ns:
                    continue
                if not device and not e.name.startswith(prefixes):
                    continue
                mid = meta.setdefault(e.name, len(meta) + 1)
                evs.append(f"events {{ metadata_id: {mid} offset_ps: "
                           f"{int(e.start_ns * 1000)} duration_ps: "
                           f"{int(e.duration_ns * 1000)} }}")
            if evs:
                lines.append(f"lines {{ id: {lid} name: {_q(line.name)} "
                             f"timestamp_ns: 0 " + " ".join(evs) + " }")
        if not lines:
            continue
        metas = " ".join(
            f"event_metadata {{ key: {mid} value {{ id: {mid} name: "
            f"{_q(name)} }} }}" for name, mid in meta.items())
        out.append(f"planes {{ id: {pid} name: {_q(plane.name)} "
                   + " ".join(lines) + " " + metas + " }")
    blob = ProfileData.text_proto_to_serialized_xspace("\n".join(out))
    with open(dst, "wb") as f:
        f.write(blob)
    return len(blob)


if __name__ == "__main__":
    src, dst, lo, hi = sys.argv[1:5]
    n = cut(src, dst, float(lo) * 1e6, float(hi) * 1e6,
            tuple(sys.argv[5:]) or ("serving/", "bench/"))
    print(f"{dst}: {n} bytes")
