"""What every plane shares: the look for a chip, the benchmark's own
spans, the profiler window, percentiles, the result line.

Nothing here knows a configuration, a traffic mix or a metric by name:
those are files found through ``BENCHMARK.json`` (see README.md).
"""
import contextlib
import glob
import importlib.util
import json
import os
import shutil
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmarks")
_LOWERING = "/jax/core/compile/jaxpr_to_mlir_module_duration"


REHEARSAL = False  # set by run.py --rehearse: no time leaves a CPU run
_TIMED = ("_ms", "_s", "_per_s", "seconds")


def log(msg, **fields):
    """An earlier line of the output (the result line is the last)."""
    if REHEARSAL:
        fields = {k: ("not measured (rehearsal)" if k.endswith(_TIMED)
                      else v) for k, v in fields.items()}
    if fields:
        msg = f"{msg} {json.dumps(fields, default=_jsonable)}"
    print(f"# {msg}", flush=True)


def _jsonable(o):
    try:
        return float(o)
    except (TypeError, ValueError):
        return repr(o)


def load_json(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_module(path, name):
    """Import one file of the benchmark by path (metric files carry a
    ``.`` in their name, which ``import`` cannot spell)."""
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None:
        raise FileNotFoundError(path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_by_name(kind, name, suffix=".py"):
    """``benchmarks/<kind>/<name><suffix>``; a split name such as
    ``device_idle_pct.gap`` falls back to its stem's file."""
    tried = []
    for cand in (name, name.rsplit(".", 1)[0]):
        path = os.path.join(HERE, kind, cand + suffix)
        tried.append(path)
        if os.path.isfile(path):
            return path
    raise FileNotFoundError(f"no {kind} file for {name!r}: tried {tried}")


def percentile(values, q):
    """Linear-interpolated q-th percentile (0..100) of a non-empty list."""
    xs = sorted(values)
    if not xs:
        raise ValueError("percentile of nothing")
    k = (len(xs) - 1) * q / 100.0
    lo = int(k)
    hi = min(lo + 1, len(xs) - 1)
    return xs[lo] + (xs[hi] - xs[lo]) * (k - lo)


class LoweringCounter:
    """Counts every program jax lowers (a hit on the persistent compile
    cache still lowers first, so this sees every new specialization).
    Copied from chip_smoke.py."""

    def __init__(self):
        import jax
        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if event == _LOWERING:
            self.n += 1


class Spans:
    """The benchmark's own spans around its calls into the program:
    seconds and calls by name, and a TraceAnnotation so that the
    profiler's trace carries them on its clock."""

    def __init__(self):
        self.seconds = {}
        self.calls = {}

    @contextlib.contextmanager
    def span(self, name):
        import jax
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench/" + name):
            yield
        dt = time.perf_counter() - t0
        self.seconds[name] = self.seconds.get(name, 0.0) + dt
        self.calls[name] = self.calls.get(name, 0) + 1

    def snapshot(self):
        return {"seconds": dict(self.seconds), "calls": dict(self.calls)}


def find_chip(chips, rehearse):
    """The devices the cell runs on, or exit non-zero with no result
    line: the measuring path never falls back to the CPU."""
    import jax
    devs = jax.devices()
    dev = devs[0]
    if not rehearse:
        if dev.platform != "tpu":
            sys.exit(f"benchmarks/run.py: jax found no TPU "
                     f"({dev.platform}:{dev.device_kind}); nothing was "
                     f"measured")
        if len(devs) < chips:
            sys.exit(f"benchmarks/run.py: the cell asks for {chips} "
                     f"chip(s), jax sees {len(devs)}")
    return devs


def peaks_for(device_kind):
    table = load_json(HERE, "peaks.json")
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"benchmarks/peaks.json; add it with its source")
    return table[device_kind]


def memory_peak_bytes(devs):
    """Peak device memory on the fullest chip, from ``memory_stats()``:
    the larger of ``peak_bytes_in_use`` and, as it stands when this is
    called (the end of the window), ``bytes_in_use + bytes_reserved``.
    On this TPU runtime a loaded program's temporaries are RESERVED and
    never counted as in use (a 124M train step: 1.8 GB in use, 12.8 GB
    reserved), so the first number alone misses most of a step's memory.
    None where the backend keeps no statistics, as the CPU does."""
    peaks = []
    for d in devs:
        stats = d.memory_stats()
        if stats and "peak_bytes_in_use" in stats:
            now = stats.get("bytes_in_use", 0) + stats.get(
                "bytes_reserved", 0)
            peaks.append(max(int(stats["peak_bytes_in_use"]), int(now)))
    return max(peaks) if peaks else None


class Tracer:
    """One profiler window. ``start``/``stop`` bracket it; ``path`` is
    the ``.xplane.pb`` it left (inside the checkout, removed by
    ``cleanup`` unless the run keeps it)."""

    def __init__(self, tag):
        self.dir = os.path.join(ROOT, ".bench_trace", tag)
        self.t_start = self.t_stop = None
        self.path = None

    def start(self):
        import jax
        shutil.rmtree(self.dir, ignore_errors=True)
        os.makedirs(self.dir, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # host spans come from annotations
        opts.host_tracer_level = 2
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.t_start = time.perf_counter()

    def stop(self):
        import jax
        self.t_stop = time.perf_counter()
        jax.profiler.stop_trace()
        found = glob.glob(os.path.join(self.dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not found:
            raise RuntimeError(f"the profiler left no .xplane.pb under "
                               f"{self.dir}")
        self.path = max(found, key=os.path.getmtime)

    def cleanup(self, keep_to=None):
        if keep_to and self.path:
            os.makedirs(os.path.dirname(keep_to), exist_ok=True)
            shutil.copyfile(self.path, keep_to)
        shutil.rmtree(self.dir, ignore_errors=True)


def rehearsed(doc):
    """A configuration or traffic file with its ``rehearse`` overrides
    applied (tiny sizes for the CPU; never used by a measuring run)."""
    out = {k: v for k, v in doc.items() if k != "rehearse"}
    for k, v in doc.get("rehearse", {}).items():
        if isinstance(v, dict) and isinstance(out.get(k), dict):
            out[k] = {**out[k], **v}
        else:
            out[k] = v
    return out
