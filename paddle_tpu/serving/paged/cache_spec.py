"""What a model keeps in the paged cache, stated by the model
(``model.cache_spec()``) and allocated by ``PagedKVPool``: the arrays a
TOKEN owns in one layer (keys and values, a latent) and the arrays a
SLOT owns (recurrent state: constant size whatever the sequence's
length). The engine's donation, ``rebind``, ``nbytes`` and the
``kv_donation`` gauge walk this instead of naming a K and a V array.
"""
import collections

import numpy as np

CacheArray = collections.namedtuple(
    "CacheArray", "name lead trail dtype layers per",
    defaults=(None, "token"))


class CacheSpec:
    """``arrays``: per-token arrays first, per-slot ones after them, in
    the order the programs take them.

    A per-token array has a name, the axes before the block's token axis
    (``lead``: a head axis, or none), the axes after it (``trail``) and a
    dtype; the pool's array is ``[layers, blocks, *lead, block_size,
    *trail]``. Its ``layers`` is ``num_layers`` unless the entry gives
    its own as a fifth field (a model in which only some layers attend).

    A per-slot array (``slot``: ``(name, layers, shape, dtype)``) is
    ``[layers, slots, *shape]``: what the layers of ONE kind keep of a
    sequence between steps. It is donated with the rest, carried and
    written in place by decode, written by prefill at the end of its
    run, and a prefill that starts a sequence (``start == 0``) takes
    zeros for it whatever the slot held before. Blocks of a model with
    such state are NOT shareable between requests (``shareable``): a
    cached prefix without the state at its boundary is a wrong answer.

    ``state`` names the small arrays the DECODE program carries beside
    the cache, ``(name, shape, dtype)``: it takes them after the cache
    arrays and returns them new each step; they are never donated, so a
    reader in another thread holds a live array whenever it looks.

    ``window`` (``(W, C)``) says that a cache ENTRY is not a position:
    a slot keeps one entry a position of its current window of ``W``
    positions and, of every window that is over, one entry a chunk of
    ``C`` positions (``text.evabyte``: a pooled key and value). The
    window's ``W`` entries become ``W / C`` when it ends, in place, so a
    slot's cache length is ``entries(positions)``, its capacity is
    counted in entries, a block is REWRITTEN while its slot lives (not
    shareable) and the pool hands a slot its blocks as it grows and
    takes them back when a window is compacted (``PagedKVPool.grow``,
    ``.shrink``).

    ``ring`` (``W``) says that the per-slot arrays are RINGS of ``W``
    entries a layer: layers that see the last ``W`` positions only keep
    position ``t`` at entry ``t % W`` and nothing else, beside the
    layers whose token arrays keep every position. Nothing is
    allocated differently for it; it tells the step loop to count what
    the rings save (``dense_bytes_per_token``)."""

    def __init__(self, num_layers, arrays, state=(), slot=(),
                 window=None, ring=None):
        import jax.numpy as jnp
        self.num_layers = int(num_layers)
        token = tuple(
            CacheArray(str(a[0]), tuple(int(d) for d in a[1]),
                       tuple(int(d) for d in a[2]), jnp.dtype(a[3]),
                       int(a[4]) if len(a) > 4 else self.num_layers,
                       "token")
            for a in arrays)
        per_slot = tuple(
            CacheArray(str(n), tuple(int(d) for d in sh), (),
                       jnp.dtype(dt), int(layers), "slot")
            for n, layers, sh, dt in slot)
        self.arrays = token + per_slot
        self.state = tuple((str(n), tuple(int(d) for d in sh),
                            jnp.dtype(dt)) for n, sh, dt in state)
        self.num_slots = None
        self.ring = None if ring is None else int(ring)
        if self.ring is not None and (self.ring < 1 or not per_slot):
            raise ValueError(f"ring {ring!r}: needs per-slot arrays of "
                             f"that many entries")
        self.window = None
        if window is not None:
            W, C = (int(d) for d in window)
            if W < 1 or C < 1 or W % C:
                raise ValueError(f"window {window!r}: the chunk must "
                                 f"divide the window")
            self.window = (W, C)

    def entries(self, positions):
        """Cache entries a slot holds once ``positions`` positions are
        in it: also the entry that position ``positions`` is written
        at. A window that is over counts ``W / C``."""
        if self.window is None:
            return int(positions)
        W, C = self.window
        return (positions // W) * (W // C) + positions % W

    def capacity(self, positions):
        """The most entries a slot holds at any moment while it is
        filled to ``positions`` positions: the last window's raw
        entries, or the window before it just before it is compacted."""
        if self.window is None or positions <= self.window[0]:
            return int(positions)
        W, C = self.window
        last = (positions - 1) // W
        return max((last - 1) * (W // C) + W,
                   self.entries(positions - 1) + 1)

    def with_slots(self, num_slots):
        """The same spec knowing how many slots there are (the pool's
        own copy does; ``shape`` of a per-slot array needs it)."""
        import copy
        out = copy.copy(self)
        out.num_slots = int(num_slots)
        return out

    def shape(self, a, num_blocks, block_size):
        if a.per == "slot":
            if self.num_slots is None:
                raise ValueError(f"{a.name} is per-slot state: its shape "
                                 f"needs the number of slots "
                                 f"(with_slots)")
            return (a.layers, self.num_slots) + a.lead
        return (a.layers, int(num_blocks)) + a.lead \
            + (int(block_size),) + a.trail

    @property
    def token_arrays(self):
        return tuple(a for a in self.arrays if a.per == "token")

    @property
    def slot_arrays(self):
        return tuple(a for a in self.arrays if a.per == "slot")

    @property
    def shareable(self):
        """Whether a cached block means the same to every request that
        reaches it: not where a slot carries state beside its blocks,
        nor where a block is rewritten in place (``window``)."""
        return not self.slot_arrays and self.window is None

    @property
    def bytes_per_token(self):
        """Useful bytes one cached token takes over all layers (what a
        device layout pads on top is not in it); with ``window`` set,
        the bytes of one ENTRY."""
        return sum(a.layers * int(np.prod(a.lead + a.trail, dtype=np.int64))
                   * a.dtype.itemsize for a in self.token_arrays)

    @property
    def bytes_per_slot(self):
        """Useful bytes of per-slot state one slot takes over all layers,
        whatever its sequence's length."""
        return sum(a.layers * int(np.prod(a.lead, dtype=np.int64))
                   * a.dtype.itemsize for a in self.slot_arrays)

    @property
    def dense_bytes_per_token(self):
        """Bytes a position would take over all layers if the layers
        that keep a ring kept every position instead (a ring entry's
        bytes a layer, as the token arrays' are); None without
        ``ring``."""
        if self.ring is None:
            return None
        return self.bytes_per_token + self.bytes_per_slot // self.ring


def kv_pair_spec(num_layers, num_heads, head_dim, dtype):
    """The GPT's spec: a K and a V array with a head axis."""
    return CacheSpec(num_layers, [("k", (num_heads,), (head_dim,), dtype),
                                  ("v", (num_heads,), (head_dim,), dtype)])
