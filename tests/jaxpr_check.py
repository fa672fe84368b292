"""What the model tests read off a traced function's jaxpr."""
import jax
import jax.numpy as jnp
import numpy as np


def rounded_projections(fn, args, weights):
    """Trace ``fn(*args)`` and, for each array of ``weights`` (leaves of
    ``args``), find the ONE ``dot_general`` that multiplies by it.
    Asserts the rounding point: the dot's result is float32 and exactly
    one equation reads it, its conversion to bfloat16 (so no rotary,
    reshape or cache write ever sees the float32). Returns the bfloat16
    values, computed by the traced equations themselves."""
    flat = jax.tree.leaves(args)
    closed = jax.make_jaxpr(fn)(*args)
    jaxpr, rounded = closed.jaxpr, []
    for w in weights:
        var = jaxpr.invars[next(i for i, a in enumerate(flat) if a is w)]
        dot, = [e for e in jaxpr.eqns if e.primitive.name == "dot_general"
                and e.invars[1] is var]
        acc = dot.outvars[0]
        assert acc.aval.dtype == jnp.float32, acc.aval
        readers = [e for e in jaxpr.eqns if any(v is acc for v in e.invars)]
        assert [e.primitive.name for e in readers] == \
            ["convert_element_type"], readers
        assert readers[0].params["new_dtype"] == jnp.bfloat16
        assert not any(v is acc for v in jaxpr.outvars)
        rounded.append(readers[0].outvars[0])
    return jax.core.eval_jaxpr(jaxpr.replace(outvars=rounded),
                               closed.consts, *flat)


def assert_same_bf16_rounding(got, x, w):
    """``got`` against the plain spelling ``jnp.dot(x, w)`` of two
    bfloat16 operands: bit for bit where this backend accumulates that
    dot in float32 and rounds once (as a TPU does), within one bfloat16
    step anywhere else."""
    assert got.dtype == jnp.bfloat16
    plain = np.asarray(jnp.dot(x, w).astype(jnp.float32))
    once = np.asarray(jnp.dot(
        x.astype(jnp.float32), w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST).astype(jnp.bfloat16)
        .astype(jnp.float32))
    got = np.asarray(got.astype(jnp.float32))
    if np.array_equal(plain, once):
        np.testing.assert_array_equal(got, plain)
    step = 2.0 ** -7 * np.maximum(np.abs(got), np.abs(plain))
    assert (np.abs(got - plain) <= step).all()
