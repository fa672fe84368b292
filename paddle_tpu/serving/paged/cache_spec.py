"""What a model keeps in the paged cache: the arrays a token owns in
one layer, stated by the model (``model.cache_spec()``) and allocated by
``PagedKVPool``. The engine's donation, ``rebind``, ``nbytes`` and the
``kv_donation`` gauge walk this instead of naming a K and a V array.
"""
import collections

import numpy as np

CacheArray = collections.namedtuple("CacheArray", "name lead trail dtype")


class CacheSpec:
    """For each array its name, the axes before the block's token axis
    (``lead``: a head axis, or none), the axes after it (``trail``) and
    the dtype; the pool's array is ``[layers, blocks, *lead, block_size,
    *trail]``. ``state`` names the small arrays the DECODE program
    carries beside the cache, ``(name, shape, dtype)``: it takes them
    after the cache arrays and returns them new each step; they are
    never donated, so a reader in another thread holds a live array
    whenever it looks."""

    def __init__(self, num_layers, arrays, state=()):
        import jax.numpy as jnp
        self.num_layers = int(num_layers)
        self.arrays = tuple(
            CacheArray(str(n), tuple(int(d) for d in le),
                       tuple(int(d) for d in tr), jnp.dtype(dt))
            for n, le, tr, dt in arrays)
        self.state = tuple((str(n), tuple(int(d) for d in sh),
                            jnp.dtype(dt)) for n, sh, dt in state)

    def shape(self, a, num_blocks, block_size):
        return (self.num_layers, int(num_blocks)) + a.lead \
            + (int(block_size),) + a.trail

    @property
    def bytes_per_token(self):
        """Useful bytes one cached token takes over all layers (what a
        device layout pads on top is not in it)."""
        return self.num_layers * sum(
            int(np.prod(a.lead + a.trail, dtype=np.int64))
            * a.dtype.itemsize for a in self.arrays)


def kv_pair_spec(num_layers, num_heads, head_dim, dtype):
    """The GPT's spec: a K and a V array with a head axis."""
    return CacheSpec(num_layers, [("k", (num_heads,), (head_dim,), dtype),
                                  ("v", (num_heads,), (head_dim,), dtype)])
