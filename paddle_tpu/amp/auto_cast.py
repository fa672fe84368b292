"""AMP autocast.

TPU-native equivalent of the reference AMP system (reference:
paddle/fluid/imperative/amp_auto_cast.h:31 AmpOperators white/black lists,
:85 AutoCastInputs; python/paddle/amp/auto_cast.py:20). On TPU the natural
low-precision dtype is bfloat16 (no loss scaling strictly required, but
GradScaler is provided for float16 parity). The cast is applied inside the
op's jitted closure so it fuses with the op (core/dispatch.py).

O1: ops on the white list run in low precision; black list stays fp32;
gray (everything else) runs in input dtype. O2: everything except the
black list runs in low precision.
"""
import threading
from contextlib import contextmanager

import jax.numpy as jnp

_state = threading.local()

# Reference white list (matmul-heavy ops benefit from MXU low precision):
# imperative/amp_auto_cast.cc default lists.
WHITE_LIST = {
    "matmul", "matmul_v2", "mul", "conv2d", "conv3d", "conv2d_transpose",
    "einsum", "bmm", "addmm", "attention", "flash_attention",
    "flash_attention_qkv",
    # the fused linear op IS a matmul (reference white list has mul/fc);
    # without it every nn.Linear ran fp32 under O1
    "linear",
    # the fused LM head accumulates in f32 internally; bf16 inputs keep
    # its vocab matmul on the bf16 MXU
    "fused_linear_cross_entropy",
}
# Ops numerically unsafe in low precision.
BLACK_LIST = {
    "exp", "log", "log2", "log10", "log1p", "pow", "square", "sqrt", "rsqrt",
    "softmax_with_cross_entropy", "cross_entropy", "log_softmax",
    "mean", "sum", "reduce_mean", "reduce_sum", "norm", "cos_sim",
    "layer_norm", "batch_norm", "softmax", "erf", "cumsum",
}


def _amp_state():
    return getattr(_state, "amp", None)


def amp_enabled():
    return _amp_state() is not None


def _cast_dtype_for(op_name):
    """Called by the dispatcher: dtype to cast float inputs to, or None."""
    st = _amp_state()
    if st is None:
        return None
    level, dtype, custom_white, custom_black = st
    if op_name in custom_black or op_name in BLACK_LIST:
        return None
    if level == "O2":
        return dtype
    if op_name in custom_white or op_name in WHITE_LIST:
        return dtype
    return None


@contextmanager
def auto_cast(enable=True, custom_white_list=None, custom_black_list=None,
              level="O1", dtype="bfloat16"):
    """paddle.amp.auto_cast equivalent."""
    if level not in ("O0", "O1", "O2"):
        raise ValueError(f"level must be O0/O1/O2, got {level}")
    jdt = {"bfloat16": jnp.bfloat16, "float16": jnp.float16}[dtype]
    prev = _amp_state()
    if enable and level != "O0":
        _state.amp = (level, jdt,
                      frozenset(custom_white_list or ()),
                      frozenset(custom_black_list or ()))
    else:
        _state.amp = None
    try:
        yield
    finally:
        _state.amp = prev


amp_guard = auto_cast
