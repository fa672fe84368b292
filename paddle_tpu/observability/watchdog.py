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
import itertools
import os
import re
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


# ------------------------------------------------- the programs' table
# What each compiled program's instructions belong to: the
# ``op_name`` metadata (jit(...)/.../mla/out/dot_general: the
# ``profiler.device_scope`` names open where the op was staged) of every
# instruction the device runs as an op of its own, keyed by what a
# profiler trace's op event can be matched on. Process-wide, filled
# where programs are built (ServingEngine._compiled hands over the
# executable's text; a to_static step hands over a producer of it, run
# when the table is first asked for or when the step is dropped),
# parsed on the first request, and readable after the engine or step
# that built a program is gone: it holds text and parsed pairs, never
# a Tensor or a buffer, and an executable no longer than its owner
# does. The newest ``_PROGRAMS_MAX`` records are kept.
_programs_lock = threading.Lock()    # the dict
_resolve_lock = threading.RLock()    # one parse (or lowering) at a time
_programs = {}   # table key -> record, see note_program
_PROGRAMS_MAX = 256
_owner_ids = itertools.count(1)

_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT )?(%?[^\s=]+) = (.*?) ([a-z][a-z0-9-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')
_COMPUTATION = re.compile(r"^(ENTRY )?(%?[^\s(]+) \(.*\{\s*$")
_MODULE = re.compile(r"^HloModule ([^\s,]+)")
# computations an instruction runs as ops of their own (a fusion's
# ``calls`` and a reducer's ``to_apply`` are inside ONE op)
_CALLED = re.compile(
    r"(?:body|condition|true_computation|false_computation)=(%?[\w.\-]+)")
_BRANCHES = re.compile(r"branch_computations=\{([^}]*)\}")
_CALLS = re.compile(r"(?:calls|to_apply)=(%?[\w.\-]+)")
# components of an op_name that are jax's own, not a scope's
_TRANSFORMS = frozenset((
    "jit", "pjit", "jvp", "transpose", "vmap", "pmap", "shard_map",
    "remat", "checkpoint", "custom_jvp", "custom_vjp", "custom_vjp_call",
    "custom_jvp_call", "while", "body", "cond", "closed_call",
    "core_call"))
_TOKEN = re.compile(r"[^/()]+\(?")
# instructions that never run as an op of their own in a trace
_NOT_AN_OP = frozenset(("parameter", "get-tuple-element", "tuple",
                        "bitcast", "constant"))


def instruction_key(text):
    """``%name = shape opcode`` of an HLO instruction: of a line of
    ``compiled.as_text()`` or of a trace's op event (whose name is the
    whole instruction), layouts dropped, so that the two meet. None for
    a line that is no instruction."""
    m = _INSTRUCTION.match(text)
    if m is None:
        return None
    name = m.group(1)
    if not name.startswith("%"):
        name = "%" + name
    return f"{name} = {_LAYOUT.sub('', m.group(2))} {m.group(3)}"


def scope_path(op_name):
    """The scope names of an ``op_name`` as a tuple of components
    (``.../bwd/block/mlp/transpose(jvp())/dot_general`` ->
    ``("bwd", "block", "mlp")``): jax's own wrappers, a jit's function
    name and the primitive at the end are left out."""
    out, skip = [], False
    tokens = _TOKEN.findall(op_name)
    for i, tok in enumerate(tokens):
        head = tok.endswith("(")
        word = tok.rstrip("(")
        if skip:            # the function name of a jit(...)
            skip = False
            if not head:
                continue
        if head:
            skip = word in ("jit", "pjit")
            if word in _TRANSFORMS:
                continue
        if word in _TRANSFORMS or i == len(tokens) - 1:
            continue
        out.append(word)
    return tuple(out)


def parse_program_text(text):
    """``(module name, {instruction_key: op_name})`` of one compiled
    program's HLO text: every instruction of the entry computation and
    of the computations it runs as ops of their own (``while`` bodies
    and conditions, branches, calls), ``""`` where an instruction has
    no metadata. A fusion carries its root's ``op_name``."""
    module, comps, entry, cur = None, {}, None, None
    for line in text.splitlines():
        if cur is None:
            m = _COMPUTATION.match(line)
            if m:
                cur = m.group(2)
                comps[cur] = []
                if m.group(1):
                    entry = cur
            elif module is None:
                mm = _MODULE.match(line)
                if mm:
                    module = mm.group(1)
        elif line.startswith("}"):
            cur = None
        else:
            comps[cur].append(line)
    out, seen, todo = {}, set(), [entry] if entry else []
    while todo:
        name = todo.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            m = _INSTRUCTION.match(line)
            if m is None:
                continue
            meta = _OP_NAME.search(line)
            out[instruction_key(line)] = meta.group(1) if meta else ""
            opcode = m.group(3)
            if opcode == "fusion":
                continue
            todo.extend(_CALLED.findall(line))
            for group in _BRANCHES.findall(line):
                todo.extend(x.strip() for x in group.split(","))
            if opcode in ("call", "async-start"):
                todo.extend(_CALLS.findall(line))
    return module, out


def new_owner():
    """A name no other builder of programs in this process has: an
    engine's compile watchdog and a to_static step's entry each take
    one, so two of them never share a record of the table."""
    return next(_owner_ids)


def note_program(key, text=None, producer=None, signature="", owner=None):
    """Keep what the program compiled under ``key`` is made of: its
    HLO ``text`` (``compiled.as_text()``), or a ``producer`` that gives
    that text when the table is first asked for (and is dropped then).
    ``owner`` (``new_owner()``) tells one builder's programs from
    another's of the same key; a later program of the same owner and
    key takes the earlier one's place. Returns the table's key."""
    name = key if isinstance(key, str) else repr(key)
    tkey = name if owner is None else f"{name}#{owner}"
    rec = {"key": name, "owner": owner, "module": None,
           "signature": signature, "text": text, "producer": producer,
           "instructions": None, "error": None}
    with _programs_lock:
        _programs.pop(tkey, None)        # a replacement is the newest
        _programs[tkey] = rec
        for old in list(_programs)[:-_PROGRAMS_MAX]:
            del _programs[old]
    return tkey


def _resolved(rec):
    """Parse a record's text (produce it first where it is a
    producer's); afterwards it holds the pairs alone."""
    if rec["instructions"] is not None or rec["error"] is not None:
        return rec
    text, producer = rec["text"], rec["producer"]
    if text is None and producer is None:
        return rec           # being resolved further up this thread
    rec["text"] = rec["producer"] = None
    try:
        if text is None:
            text = producer()
        rec["module"], rec["instructions"] = parse_program_text(text)
    except Exception as e:  # noqa: BLE001 - an observer never raises
        rec["instructions"] = {}
        rec["error"] = f"{type(e).__name__}: {e}"[:400]
    return rec


def resolve_program(tkey):
    """Produce and parse the record ``tkey`` now, where it still waits
    for the first request: what a to_static step's entry does when it
    is dropped, so that the record stops holding its producer (and
    with it the step's executable)."""
    with _programs_lock:
        rec = _programs.get(tkey)
    if rec is not None:
        with _resolve_lock:
            _resolved(rec)


def program_scopes():
    """``{table key: {"key": <program key>, "owner": ..., "module":
    <HLO module name, the program's name in a trace>, "signature": ...,
    "instructions": {instruction_key: op_name}}}`` for every program
    this process has built through an engine's AOT table or a to_static
    step (the table key is the program key, ``#owner`` appended). The
    first call parses (and asks a to_static step's producer for its
    text); a record that could not be read has no instructions and
    says why under ``"error"``."""
    with _programs_lock:
        recs = list(_programs.items())
    out = {}
    for tkey, rec in recs:
        # not under the dict's lock: a producer may take seconds, and
        # an engine that compiles meanwhile must not wait
        with _resolve_lock:
            rec = _resolved(rec)
        out[tkey] = {k: rec[k] for k in (
            "key", "owner", "module", "signature", "instructions",
            "error")}
    return out


def ambiguous_instructions(table):
    """The instruction keys that two programs of ``table`` (a
    ``program_scopes()``, or the part of it that ran) hold under
    DIFFERENT scopes: a trace's op event of that key cannot be given to
    either, so a reader counts it as unscoped."""
    first, out = {}, set()
    for rec in table.values():
        for ikey, op_name in rec["instructions"].items():
            path = scope_path(op_name)
            if first.setdefault(ikey, path) != path:
                out.add(ikey)
    return out


def programs_report(events=None, owner=None):
    """The operator's view (``GET /debug/programs``): per program (of
    the builder ``owner`` with its compile ``events``: an engine's own;
    else all) its module name, signature, and the count of instructions
    per scope (``"/"``-joined ``scope_path``; ``"(none)"`` for an
    instruction with no scope; parameters, tuples, bitcasts and
    constants, which never run as an op, left out), joined to the
    watchdog's compile ``events`` of the same key (cost, memory). No
    seconds: device time by scope needs a capture, see
    ``benchmarks/tools/scope_report.py``.
    """
    table = program_scopes()
    if events is not None:
        table = {k: rec for k, rec in table.items()
                 if rec["owner"] == owner}
    amb = ambiguous_instructions(table)
    by_key = {e["key"]: e for e in events or ()}   # the newest build
    programs = {}
    for tkey, rec in table.items():
        counts = {}
        for ikey, op_name in rec["instructions"].items():
            if ikey.rsplit(" ", 1)[-1] in _NOT_AN_OP:
                continue
            name = "/".join(scope_path(op_name)) or "(none)"
            if ikey in amb:
                name = "(ambiguous)"
            counts[name] = counts.get(name, 0) + 1
        e = by_key.get(rec["key"], {})
        programs[rec["key"] if events is not None else tkey] = {
            "module": rec["module"], "signature": rec["signature"],
            "error": rec["error"],
            "instructions": sum(counts.values()),
            "instructions_by_scope": dict(sorted(counts.items())),
            "cost": e.get("cost"), "memory": e.get("memory"),
            "call_site": e.get("call_site")}
    return {"programs": programs}


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
        self.id = new_owner()   # its programs' owner in the table
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
