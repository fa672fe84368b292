"""Program-capture (trace) context.

TPU-native replacement for the reference's ProgramDesc+Executor static graph
and the dygraph-to-static ProgramTranslator (reference:
python/paddle/fluid/dygraph/dygraph_to_static/program_translator.py:232,
paddle/fluid/framework/executor.cc:166). Instead of building an op-desc
program and interpreting it, we capture the user's Python step function as a
single XLA computation via jax.jit:

- phase "record": the function runs eagerly while we record which
  pre-existing Tensors it reads (-> compiled-function inputs) and which it
  mutates (-> compiled-function outputs, written back after each call).
  This discovers closure state (parameters, optimizer moments, RNG state)
  without requiring the user to thread it functionally.
- phase "jit": the function runs under jax.jit; reads of captured tensors
  return the corresponding tracer, mutations are collected as extra outputs.

Mutation of a Tensor means assignment to its `.value` — paddle's in-place
ops (optimizer updates, set_value) are expressed that way, which maps
in-place semantics onto XLA's functional model with buffer donation.
"""
import threading
import weakref

_state = threading.local()

# Static-analysis hooks (paddle_tpu.analysis.birth): None by default so
# the untraced hot path pays ONE attribute test. When birth tracking is
# enabled, _birth_hook(tensor) records a birth site for every Tensor
# constructed under a trace, and _capture_hook(ctx, tensor) runs when a
# read is about to CAPTURE a pre-existing tensor (record-mode read /
# jit-mode constant embed) — the escape point of a tracer leak.
_birth_hook = None
_capture_hook = None


def current_trace():
    return getattr(_state, "trace", None)


def in_compiled_step():
    """True while a compiled (to_static) step is being traced — the only
    place mesh-wide sharding is applied: eager and record phases stay on
    one device (no eager sub-group collectives, and a multi-device eager
    program cannot hold a Mosaic kernel)."""
    ctx = current_trace()
    return ctx is not None and ctx.mode == "jit"


def adopt(tensor):
    """Register a freshly constructed constant Tensor with the innermost
    active trace when its value is a tracer.

    Constant-creating op paths (scalar wrapping, clip bounds, ...) build
    Tensors directly instead of going through the op dispatcher, which
    registers its outputs. Inside a lax sub-trace (cond/while bodies)
    jnp.asarray of a python scalar yields a TRACER of that sub-trace; if
    the Tensor holding it is not registered as trace-created, the
    TraceContext later classifies it as a pre-existing capture and the
    dead sub-trace tracer escapes into the outer replay
    (UnexpectedTracerError). Concrete values keep today's capture
    semantics untouched — only tracer-valued constants are adopted."""
    ctx = current_trace()
    if ctx is not None:
        import jax.core as jcore
        if isinstance(tensor._value, jcore.Tracer):
            ctx.register_created(tensor)
    return tensor


class TraceContext:
    def __init__(self, mode):
        assert mode in ("record", "jit")
        self.mode = mode
        # id(tensor) -> tensor, for pre-existing tensors read during the run
        self.reads = {}
        # id(tensor) -> tensor, for pre-existing tensors mutated during the run
        self.writes = {}
        # id(tensor) -> weakref, for tensors created during this run
        # (their reads are internal). Membership MUST be checked through
        # is_created(): a dead created tensor's id can be recycled by a
        # later allocation, and a raw id test would silently classify
        # the newcomer as trace-created.
        self.created = {}
        self.created_refs = []
        # jit phase: id(tensor) -> current traced value (tracer)
        self.values = {}
        self.captured_ids = set()

    # -- called from Tensor.value property --------------------------------
    def read(self, tensor):
        tid = id(tensor)
        if tid in self.values:
            return self.values[tid]
        if self.is_created(tensor):
            # created during this very trace but its raw value still set
            return tensor._value
        if self.mode == "record":
            if tensor._value is None:
                raise RuntimeError(
                    f"Tensor {tensor.name!r} read inside a traced function but it "
                    "has no value (it may have escaped a previous trace)")
            if _capture_hook is not None:
                _capture_hook(self, tensor)
            self.reads[tid] = tensor
            return tensor._value
        # jit mode: not captured -> embed as a compile-time constant
        if tensor._value is None:
            raise RuntimeError(
                f"Tensor {tensor.name!r} read inside jit trace has no concrete "
                "value; it likely escaped a previous trace. Make sure the traced "
                "step is self-contained (backward + step + clear_grad inside).")
        if _capture_hook is not None:
            _capture_hook(self, tensor)
        return tensor._value

    def write(self, tensor, value):
        tid = id(tensor)
        if not self.is_created(tensor):
            self.writes[tid] = tensor
        if self.mode == "record":
            tensor._value = value
        else:
            self.values[tid] = value

    def register_created(self, tensor):
        ref = weakref.ref(tensor)
        self.created[id(tensor)] = ref
        self.created_refs.append(ref)

    def is_created(self, tensor):
        """Was THIS tensor (identity, not recycled id) created during
        the trace?"""
        ref = self.created.get(id(tensor))
        return ref is not None and ref() is tensor

    # -- jit phase helpers -------------------------------------------------
    def bind(self, tensor, tracer):
        self.values[id(tensor)] = tracer
        self.captured_ids.add(id(tensor))

    def final_value(self, tensor):
        return self.values.get(id(tensor), tensor._value)


class _Guard:
    def __init__(self, ctx):
        self.ctx = ctx

    def __enter__(self):
        if current_trace() is not None:
            raise RuntimeError("nested traces are not supported")
        _state.trace = self.ctx
        return self.ctx

    def __exit__(self, *exc):
        _state.trace = None
        if self.ctx.mode == "jit":
            # Poison tensors created during the jit trace whose value is a
            # tracer: they must not be read outside the trace.
            import jax.core as jcore
            for ref in self.ctx.created_refs:
                t = ref()
                if t is None:
                    continue
                v = self.ctx.values.get(id(t), t._value)
                if isinstance(v, jcore.Tracer):
                    t._value = None
        return False


def trace_guard(ctx):
    return _Guard(ctx)
