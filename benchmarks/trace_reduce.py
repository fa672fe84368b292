"""From a profiler trace (``.xplane.pb``) to numbers.

Read with ``jax.profiler.ProfileData`` alone. What a TPU trace holds
(looked at by hand, PR 24): one plane per chip named ``/device:TPU:<n>``
whose line ``XLA Modules`` has one event per execution of a compiled
program (``jit_<fn>(<fingerprint>)``) and whose line ``XLA Ops`` has one
event per executed HLO op (ops inside a ``while`` body nest under the
``while`` event on the same line); and host planes (``/host:CPU``) with
one line per thread, on which ``jax.profiler.TraceAnnotation`` scopes
appear by their name. Device and host events share one clock.

busy      union of the device's op intervals (module intervals where a
          trace has no op line) inside the window, per chip, averaged
idle gaps the complement; each gap goes to the host span that covers
          most of it (innermost first), so "where the chip waited" reads
          in the program's own words
programs  executions and device seconds per compiled program
ops       self time per op name: an op's duration minus the ops nested
          inside it, so a ``while`` does not count its body twice
"""
import re

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINES = ("XLA Ops",)
MODULE_LINES = ("XLA Modules",)
_FINGERPRINT = re.compile(r"\(\d+\)$")


def _events(line):
    """[(start_ns, end_ns, name)] sorted by start (longest first on a
    tie, so that a parent precedes its children)."""
    out = [(float(e.start_ns), float(e.start_ns + e.duration_ns), e.name)
           for e in line.events]
    out.sort(key=lambda e: (e[0], -e[1]))
    return out


def union(intervals):
    """Merged, sorted [(start, end)] of possibly overlapping ones."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            if e > merged[-1][1]:
                merged[-1][1] = e
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def self_times(events):
    """name -> [self seconds, calls] for nested events on one line."""
    out = {}
    stack = []  # [end, name, self_ns]

    def close(upto):
        while stack and stack[-1][0] <= upto:
            end, name, own = stack.pop()
            rec = out.setdefault(name, [0.0, 0])
            rec[0] += max(own, 0.0) / 1e9
            rec[1] += 1

    for s, e, name in events:
        close(s)
        if stack:
            stack[-1][2] -= (min(e, stack[-1][0]) - s)
        stack.append([e, name, e - s])
    close(float("inf"))
    return out


_OP = re.compile(r"^(%[^ ]+) = (.*?) ([a-z][a-z0-9-]*)\(")


def op_label(event_name, limit=120):
    """``%fusion.24 fusion f32[50304,768]...`` from the whole HLO
    instruction a TPU trace uses as an op's name; a custom call keeps
    its target."""
    m = _OP.match(event_name)
    if not m:
        return event_name[:limit]
    label = f"{m.group(1)} {m.group(3)} {m.group(2)}"
    target = re.search(r'custom_call_target="([^"]+)"', event_name)
    if target:
        label = f"{m.group(1)} {m.group(3)}:{target.group(1)} {m.group(2)}"
    return label[:limit]


def program_name(module_event_name):
    return _FINGERPRINT.sub("", module_event_name)


def _clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def _line(plane, names):
    for line in plane.lines:
        if line.name in names:
            return line
    return None


def host_spans(data, prefixes):
    """[(start, end, name)] of annotation scopes on any host thread
    whose name starts with one of ``prefixes``."""
    spans = []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(tuple(prefixes)):
                    spans.append((float(e.start_ns),
                                  float(e.start_ns + e.duration_ns),
                                  e.name))
    spans.sort(key=lambda s: (s[0], -s[1]))
    return spans


def attribute_gaps(gaps, spans):
    """name -> idle seconds. A gap goes to the span covering most of
    it; among equals the shortest (innermost) span wins; a gap no span
    touches goes to ``(no host span)``."""
    out = {}
    j0 = 0
    for gs, ge in gaps:
        while j0 < len(spans) and spans[j0][1] < gs - 5e8:
            j0 += 1          # spans ending >0.5 s before: done with
        best, best_key = "(no host span)", (0.0, 0.0)
        j = j0
        while j < len(spans) and spans[j][0] < ge:
            ss, se, name = spans[j]
            cover = min(ge, se) - max(gs, ss)
            if cover > 0:
                key = (cover, -(se - ss))
                if key > best_key:
                    best, best_key = name, key
            j += 1
        out[best] = out.get(best, 0.0) + (ge - gs) / 1e9
    return out


def reduce(path, span_prefixes=(), window=None):
    """The whole reduction of one trace file. ``window`` (start_ns,
    end_ns) clips it; by default the window is the extent of the
    device's own events."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    chips = []
    for plane in data.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        ops_line = _line(plane, OPS_LINES)
        mod_line = _line(plane, MODULE_LINES)
        ops = _events(ops_line) if ops_line is not None else []
        mods = _events(mod_line) if mod_line is not None else []
        if ops or mods:
            chips.append({"plane": plane.name, "ops": ops, "mods": mods})
    if not chips:
        raise ValueError(f"{path}: no device plane with events "
                         f"(planes: {[p.name for p in data.planes]})")
    if window is None:
        every = [e for c in chips for e in c["ops"] + c["mods"]]
        window = (min(e[0] for e in every), max(e[1] for e in every))
    lo, hi = window
    spans = host_spans(data, span_prefixes) if span_prefixes else []

    busy, programs, ops_self, gaps_by = [], {}, {}, {}
    for c in chips:
        base = c["ops"] or c["mods"]
        merged = _clip(union([(s, e) for s, e, _ in base]), lo, hi)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        edges = [lo] + [x for s, e in merged for x in (s, e)] + [hi]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        for name, sec in attribute_gaps(gaps, spans).items():
            gaps_by[name] = gaps_by.get(name, 0.0) + sec / len(chips)
        for s, e, name in c["mods"]:
            if e <= lo or s >= hi:
                continue
            rec = programs.setdefault(program_name(name),
                                      {"calls": 0, "seconds": 0.0,
                                       "durations_s": []})
            rec["calls"] += 1
            rec["seconds"] += (min(e, hi) - max(s, lo)) / 1e9
            if s >= lo and e <= hi:     # whole executions only
                rec["durations_s"].append((e - s) / 1e9)
        inside = [ev for ev in c["ops"] if ev[1] > lo and ev[0] < hi]
        for name, (sec, calls) in self_times(inside).items():
            rec = ops_self.setdefault(name, [0.0, 0])
            rec[0] += sec / len(chips)
            rec[1] += calls
    return {
        "chips": len(chips),
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy) / len(busy),
        "programs": programs,
        "ops": {k: {"seconds": v[0], "calls": v[1]}
                for k, v in ops_self.items()},
        "idle_by_span": gaps_by,
    }


def breakdown(red, top=10):
    """The result line's ``breakdown``: the device operations that took
    most time and the longest idle shares by host span."""
    ops = sorted(((op_label(k), v["seconds"])
                  for k, v in red["ops"].items()),
                 key=lambda kv: -kv[1])[:top]
    gaps = sorted(red["idle_by_span"].items(), key=lambda kv: -kv[1])[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[k, v] for k, v in gaps]}


def median_execution_s(red, substring):
    """Median device seconds of one whole execution, or None."""
    durs = sorted(program_seconds(red, substring)[2])
    if not durs:
        return None
    mid = len(durs) // 2
    return durs[mid] if len(durs) % 2 else (durs[mid - 1] + durs[mid]) / 2


def program_seconds(red, substring):
    """(device seconds inside the window, executions, durations of the
    executions that lie wholly inside it) of the programs whose name
    contains ``substring``."""
    sec, calls, durs = 0.0, 0, []
    for name, rec in red["programs"].items():
        if substring in name:
            sec += rec["seconds"]
            calls += rec["calls"]
            durs += rec["durations_s"]
    return sec, calls, durs
