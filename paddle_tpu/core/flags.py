"""Process-level flags, paddle.set_flags / get_flags style.

TPU-native equivalent of the reference gflags registry (reference:
paddle/fluid/platform/flags.cc:33-353 and
paddle/fluid/pybind/global_value_getter_setter.cc). Flags can be set via
environment (FLAGS_xxx=...) or paddle_tpu.set_flags({...}).
Only flags meaningful on the XLA/PjRt runtime are kept; CUDA-specific ones
are accepted but ignored for compatibility.
"""
import os

_DEFAULTS = {
    # debugging: scan op outputs for NaN/Inf (flags.cc:44 FLAGS_check_nan_inf)
    "FLAGS_check_nan_inf": False,
    # deterministic execution (flags.cc:108 FLAGS_cudnn_deterministic analogue):
    # on TPU, XLA is deterministic by default; flag kept for API parity.
    "FLAGS_deterministic": True,
    # eager op dispatch: log compiles (debugging aid, no reference analogue)
    "FLAGS_log_compiles": False,
    # DDP/DP gradient fusion bucket size in MB (reference reducer.h:84
    # group_size_limits ~25MB)
    "FLAGS_fuse_parameter_memory_size": 25.0,
    # persistent XLA compilation cache directory ("" disables). Eager
    # dispatch compiles one executable per (op, shape); on TPU those
    # compiles dominate warmup (SURVEY §7 hard-part 1) — the disk cache
    # amortizes them across processes/runs. One FIXED path inside the
    # checkout: the path is part of jax's cache key, so a directory
    # that moves never hits. $JAX_COMPILATION_CACHE_DIR, when set,
    # places the cache from outside (see init_compilation_cache).
    "FLAGS_compilation_cache_dir": os.path.join(
        os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__)))), ".jax_cache"),
    # only cache compiles slower than this (seconds)
    "FLAGS_compilation_cache_min_compile_secs": 0.3,
    # lazy micro-tracing eager executor (core/lazy.py): defer eager ops
    # into a micro-graph flushed as one cached XLA executable at
    # materialization/step boundaries. The TPU answer to the reference's
    # generated fast eager entry points (op_function_generator.cc:519).
    "FLAGS_lazy_eager": True,
}


def init_compilation_cache():
    """Apply FLAGS_compilation_cache_dir to jax (called at import and
    whenever set_flags changes the cache flags).

    Precedence: an explicit FLAGS_compilation_cache_dir (environment or
    set_flags; "" disables) > $JAX_COMPILATION_CACHE_DIR, which jax
    reads itself — then NO directory is set in code > the fixed
    in-checkout default. An unusable directory raises."""
    import jax
    jax.config.update(
        "jax_persistent_cache_min_compile_time_secs",
        float(get_flag("FLAGS_compilation_cache_min_compile_secs")))
    explicit = ("FLAGS_compilation_cache_dir" in _flags
                or "FLAGS_compilation_cache_dir" in os.environ)
    if not explicit and os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    path = get_flag("FLAGS_compilation_cache_dir")
    if not path:
        jax.config.update("jax_compilation_cache_dir", None)
        return
    os.makedirs(path, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", path)


_flags = {}


def _coerce(default, v):
    if isinstance(default, bool):
        if isinstance(v, str):
            return v.lower() in ("1", "true", "yes", "on")
        return bool(v)
    if isinstance(default, float):
        return float(v)
    if isinstance(default, int):
        return int(v)
    return v


def get_flag(name):
    if name in _flags:
        return _flags[name]
    env = os.environ.get(name)
    default = _DEFAULTS.get(name)
    if env is not None:
        return _coerce(default if default is not None else env, env)
    return default


_CACHE_FLAGS = ("FLAGS_compilation_cache_dir",
                "FLAGS_compilation_cache_min_compile_secs")


def set_flags(flags):
    """paddle.set_flags({'FLAGS_check_nan_inf': 1})"""
    reinit_cache = any(k in _CACHE_FLAGS for k in flags)
    for k, v in flags.items():
        default = _DEFAULTS.get(k)
        _flags[k] = _coerce(default, v) if default is not None else v
    if reinit_cache:
        init_compilation_cache()


def get_flags(names):
    if isinstance(names, str):
        names = [names]
    return {n: get_flag(n) for n in names}
