"""Shared Pallas/Mosaic compatibility helpers.

The framework enables jax_enable_x64 globally (paddle int64/float64
dtype semantics, core/__init__.py); inside Pallas kernels and their
BlockSpec index maps python literals would then become i64/f64, which
Mosaic cannot lower ("failed to legalize operation 'func.return'",
observed on the real chip). Every Pallas entry point traces in 32-bit
mode via this decorator.
"""
import functools

import jax

def trace_32bit(fn):
    """Run `fn` (a pallas_call builder) with x64 disabled."""
    @functools.wraps(fn)
    def wrapper(*args, **kwargs):
        with jax.enable_x64(False):
            return fn(*args, **kwargs)
    return wrapper
