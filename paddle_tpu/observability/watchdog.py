"""Compile watchdog: attributed compile accounting + a steady-state
recompile alarm.

The serving engine's zero-recompile steady state (ROADMAP, PR 1/2) is
an AOT-table construction property — but in production the thing you
need when it BREAKS is attribution: which call-site compiled, with
what abstract-shape signature, and was the system supposed to be warm.
The watchdog records every compile event (key, signature, call-site,
warm/cold) and, once ``declare_warmup_complete()`` is called, flags —
or raises, in ``mode="raise"`` — any further compile, carrying the
full attribution in the report/exception instead of a bare counter
drift.

Two integration points:

  * the engine's AOT table (ServingEngine._compiled) records every
    executable build directly — ``metrics.compiles`` stays the exact
    counter, the watchdog makes it attributable and testable;
  * ``watch_jax_lowering(watchdog)`` patches the generic
    ``jax.stages.Lowered.compile`` AOT entry point for the duration of
    a ``with`` block, so any lowering-based compile in scope (training
    AOT paths, third-party code) is captured without its cooperation.

Compile records also carry DEVICE COST telemetry: the engine attaches
``compiled.cost_analysis()`` (flops, bytes accessed — via
``executable_cost()``) and ``device.memory_stats()`` (HBM in-use /
limit — via ``device_memory_stats()``) to each event with
``annotate()``. Both helpers are best-effort: backends that don't
report (CPU has no memory_stats; some runtimes hide cost_analysis)
yield None, never an exception — the graceful-fallback contract the
serving engine and bench artifacts rely on.
"""
import contextlib
import hashlib
import os
import threading
import traceback

_SELF = os.path.basename(__file__)


class CompileAfterWarmupError(RuntimeError):
    """A compile happened after warmup was declared complete — the
    zero-recompile invariant broke. The message carries the full
    attribution (key, abstract-shape signature, call-site)."""


def abstract_signature(args, max_leaves_shown=6):
    """Stable abstract-shape signature of a pytree of arrays: a short
    human-readable prefix (first few leaves as dtype[shape]) plus a
    digest over ALL leaves — two argument sets get the same signature
    iff every leaf matches in dtype and shape."""
    try:
        import jax
        leaves = jax.tree_util.tree_leaves(args)
    except Exception:  # pragma: no cover - jax always present here
        leaves = list(args) if isinstance(args, (list, tuple)) else [args]
    parts = []
    for leaf in leaves:
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is None:
            parts.append(type(leaf).__name__)
        else:
            dims = ",".join(str(d) for d in shape)
            parts.append(f"{dtype}[{dims}]")
    digest = hashlib.sha1("|".join(parts).encode()).hexdigest()[:12]
    shown = ";".join(parts[:max_leaves_shown])
    more = len(parts) - max_leaves_shown
    if more > 0:
        shown += f";+{more} leaves"
    return f"{shown}#{digest}"


def executable_cost(compiled):
    """Best-effort device cost model of one compiled executable:
    ``{"flops": ..., "bytes_accessed": ...}`` (floats, per execution)
    from ``compiled.cost_analysis()``; None when the backend doesn't
    report. jax returns either a dict or a one-element list of dicts
    depending on version — both shapes are handled."""
    try:
        analysis = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(analysis, (list, tuple)):
        analysis = analysis[0] if analysis else None
    if not isinstance(analysis, dict):
        return None
    out = {}
    for src, dst in (("flops", "flops"),
                     ("bytes accessed", "bytes_accessed"),
                     ("optimal_seconds", "optimal_seconds")):
        v = analysis.get(src)
        if isinstance(v, (int, float)) and v == v and v >= 0:
            out[dst] = float(v)
    return out or None


def executable_memory(compiled):
    """Best-effort ``compiled.memory_analysis()`` of one executable as
    ``{"alias_bytes": ..., "temp_bytes": ...}`` (ints): the bytes of
    donated arguments the program really updates in place, and the
    temporaries it holds beside its arguments while it runs. None
    where the backend's executable gives no memory analysis."""
    try:
        m = compiled.memory_analysis()
        return {"alias_bytes": int(m.alias_size_in_bytes),
                "temp_bytes": int(m.temp_size_in_bytes)}
    except Exception:
        return None


def device_memory_stats(device=None):
    """Best-effort ``device.memory_stats()`` as a JSON-safe dict of
    numeric fields (PJRT reports e.g. bytes_in_use / bytes_limit /
    peak_bytes_in_use on TPU/GPU); None where the backend doesn't
    report (CPU). Adds ``bytes_free`` (limit - in_use, the HBM
    headroom) when both sides are present."""
    try:
        if device is None:
            import jax
            device = jax.devices()[0]
        stats = device.memory_stats()
    except Exception:
        return None
    if not isinstance(stats, dict):
        return None
    out = {k: v for k, v in stats.items()
           if isinstance(v, (int, float)) and v == v}
    if not out:
        return None
    if "bytes_limit" in out and "bytes_in_use" in out:
        out["bytes_free"] = out["bytes_limit"] - out["bytes_in_use"]
    return out


def _call_site(skip=0):
    """Innermost stack frame outside this module, after skipping
    ``skip`` additional frames (the engine skips its own _compiled
    helper so attribution lands on the dispatch line that triggered
    the build)."""
    frames = [fr for fr in traceback.extract_stack()
              if os.path.basename(fr.filename) != _SELF]
    if not frames:
        return "<unknown>"
    idx = max(0, len(frames) - 1 - skip)
    fr = frames[idx]
    return f"{fr.filename}:{fr.lineno} ({fr.name})"


class CompileWatchdog:
    """Attributed compile log with a declared-warmup alarm.

    ``mode="flag"`` (default) records steady-state compiles and
    surfaces them in ``report()``; ``mode="raise"`` additionally
    raises CompileAfterWarmupError at the offending record() — the
    hard-fail setting for tests and canary deployments.
    """

    def __init__(self, mode="flag"):
        if mode not in ("flag", "raise"):
            raise ValueError(f"mode must be 'flag' or 'raise', got "
                             f"{mode!r}")
        self.mode = mode
        self._lock = threading.Lock()
        self._events = []
        self._warmed = False

    # ------------------------------------------------------- recording
    def record(self, key, signature="", call_site=None, skip=0):
        """Log one compile. ``key`` identifies the executable (the
        engine uses its AOT-table key), ``signature`` the abstract
        shapes it was built for; ``call_site`` defaults to the caller's
        file:line (``skip`` walks further out for wrapper helpers).
        Returns the event dict; raises in mode='raise' when warm."""
        if call_site is None:
            call_site = _call_site(skip=skip)
        with self._lock:
            event = {
                "seq": len(self._events),
                "key": key if isinstance(key, str) else repr(key),
                "signature": signature,
                "call_site": call_site,
                "steady_state": self._warmed,
                # post-compile device telemetry, attached via
                # annotate() once the executable exists (record() runs
                # BEFORE the build so mode="raise" prevents it)
                "cost": None,
                "memory": None,
            }
            self._events.append(event)
            warmed = self._warmed
        if warmed and self.mode == "raise":
            raise CompileAfterWarmupError(
                f"compile after declared warmup: key={event['key']} "
                f"signature={signature} at {call_site}")
        return event

    def annotate(self, seq, **extra):
        """Attach post-compile facts (device cost analysis, memory
        stats) to an already-recorded event by its ``seq``. JSON-safe
        values only — the events feed report() straight into bench
        artifacts."""
        with self._lock:
            self._events[seq].update(extra)

    def declare_warmup_complete(self):
        """From here on, every compile is a steady-state violation."""
        with self._lock:
            self._warmed = True

    def reopen_warmup(self):
        """Re-enter warmup (supervisor restart): the rebuilt AOT
        table's compiles are recovery work, not steady-state
        violations — the supervisor re-declares warmup once the replay
        drains, so the alarm re-arms the moment recovery completes.
        Already-flagged events keep their steady_state attribution."""
        with self._lock:
            self._warmed = False

    # -------------------------------------------------------- querying
    @property
    def warmed(self):
        return self._warmed

    @property
    def compiles(self):
        with self._lock:
            return len(self._events)

    def events(self):
        with self._lock:
            return [dict(e) for e in self._events]

    def steady_state_events(self):
        return [e for e in self.events() if e["steady_state"]]

    def signature_groups(self):
        """Compile signatures grouped by executable key — the feed for
        the analysis ``dynamic-shape-risk`` lint pass: one key compiled
        under more than one distinct abstract-shape signature means the
        same logical executable re-specialized per input shape (the
        python-int-shape-derived-from-traced-values recompile source),
        attributed by the recorded dispatch call-sites."""
        with self._lock:
            groups = {}
            for e in self._events:
                g = groups.setdefault(
                    e["key"], {"signatures": [], "call_sites": []})
                if e["signature"] not in g["signatures"]:
                    g["signatures"].append(e["signature"])
                if e["call_site"] not in g["call_sites"]:
                    g["call_sites"].append(e["call_site"])
            return groups

    def report(self):
        """JSON-ready summary — the bench artifact's ``watchdog``
        section and the test surface for the zero-recompile
        invariant."""
        events = self.events()
        steady = [e for e in events if e["steady_state"]]
        return {
            "warmed": self._warmed,
            "mode": self.mode,
            "compiles_total": len(events),
            "warmup_compiles": len(events) - len(steady),
            "steady_state_compiles": len(steady),
            "events": events,
            "steady_state_events": steady,
        }


@contextlib.contextmanager
def watch_jax_lowering(watchdog):
    """Patch the generic ``jax.stages.Lowered.compile`` AOT entry
    point so every lowering compiled inside the block is recorded in
    ``watchdog`` with its in_avals signature and call-site. Restores
    the original on exit; reentrant use nests harmlessly (each level
    records once — the patch chain unwinds in reverse)."""
    import jax

    cls = jax.stages.Lowered
    original = cls.compile

    def compile(self, *args, **kwargs):  # noqa: A002 - jax's name
        executable = original(self, *args, **kwargs)
        try:
            avals = getattr(self, "in_avals", None)
            signature = str(avals)[:400] if avals is not None else ""
        except Exception:
            signature = ""
        # the patched frame lives in this file and is filtered out of
        # the stack walk already, so skip=0 lands on the caller
        watchdog.record("jax.Lowered.compile", signature=signature)
        return executable

    cls.compile = compile
    try:
        yield watchdog
    finally:
        cls.compile = original
