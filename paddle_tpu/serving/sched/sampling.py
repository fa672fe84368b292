"""Per-slot sampling for the pooled decode step.

``generate()`` samples (temperature / top-k) per CALL; the serving
engine decodes every slot through ONE compiled executable, so sampling
has to be per SLOT inside that one program — a greedy chat request and
a temperature-0.8 creative request share a dispatch. Everything here
keeps the zero-recompile invariant:

  * sampling parameters are plain ``[num_slots]`` device arrays
    (``SlotSampler`` — host-authored, snapshot-uploaded when an
    admission dirtied them, the block-table discipline), so parameter
    variety never changes the compiled signature;
  * randomness needs NO threaded key state: each slot's key derives
    from ``fold_in(PRNGKey(seed[slot]), position)`` — the position a
    token is emitted at is already per-slot device state (``pos``), so
    the stream is deterministic per (request seed, token index),
    reproducible across engine runs, schedules, and chunked vs
    unchunked prefill;
  * greedy stays the default and the bit-exact ``generate()`` parity
    path: a slot with ``temperature <= 0`` (or ``top_k == 1``,
    ``generate()``'s own greedy condition) takes ``argmax`` — sampled
    and greedy slots coexist in the same dispatch.

Semantics match ``generate()``: logits / temperature, keep-ties top-k
(``lg < kth`` masking), then ``jax.random.categorical``. ``top_p``
(nucleus) extends the same masking scheme: keep the smallest
probability-sorted set whose cumulative mass reaches ``top_p``.
top-k and top-p compose (both masks apply); the per-slot ``k`` and
``p`` are TRACED values — one sort of the logits serves both, so
parameter variety costs zero compiles.
"""
import numpy as np

MASKED = -1e30


def build_sampling_head(vocab_size):
    """Returns ``sample(logits, seeds, key_idx, temps, topks, topps)``
    mapping ``[N, V]`` logits to ``[N]`` int32 tokens. ``seeds`` /
    ``key_idx`` / ``topks`` int32, ``temps`` / ``topps`` float32, all
    ``[N]`` and traced. ``temps <= 0`` or ``topks == 1`` selects the
    greedy argmax for that row; ``topks <= 0`` disables top-k;
    ``topps >= 1`` disables top-p."""
    import jax
    import jax.numpy as jnp

    V = int(vocab_size)

    def sample(logits, seeds, key_idx, temps, topks, topps):
        greedy = (temps <= 0.0) | (topks == 1)
        lg = logits / jnp.maximum(temps, 1e-6)[:, None]
        srt = jnp.sort(lg, axis=-1)[:, ::-1]               # desc [N, V]
        # top-k: mask strictly below the kth largest (ties at the kth
        # value stay, matching generate()'s lax.top_k threshold)
        k = jnp.clip(topks, 1, V)
        kth = jnp.take_along_axis(srt, (k - 1)[:, None], axis=1)
        mask_k = jnp.where((topks > 0)[:, None], lg < kth, False)
        # top-p: in sorted order keep rows whose PRECEDING cumulative
        # probability is still below p (the first row always stays);
        # the smallest kept logit is the admission threshold
        probs = jax.nn.softmax(srt, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        keep = (cum - probs) < topps[:, None]
        pthresh = jnp.min(jnp.where(keep, srt, jnp.inf), axis=-1)
        mask_p = jnp.where((topps < 1.0)[:, None],
                           lg < pthresh[:, None], False)
        lg = jnp.where(mask_k | mask_p, MASKED, lg)
        keys = jax.vmap(
            lambda sd, i: jax.random.fold_in(jax.random.PRNGKey(sd), i)
        )(seeds, key_idx)
        drawn = jax.vmap(jax.random.categorical)(keys, lg)
        return jnp.where(greedy, jnp.argmax(logits, -1),
                         drawn).astype(jnp.int32)

    return sample


def request_sampling_params(req):
    """(seed, temperature, top_k, top_p) the programs consume for one
    request — greedy requests normalize to the all-disabled tuple so a
    slot recycled from a sampled occupant can never inherit noise."""
    if getattr(req, "sampled", False):
        return (int(req.seed), float(req.temperature), int(req.top_k),
                float(req.top_p))
    return (0, 0.0, 0, 1.0)


class SlotSampler:
    """Host-authored per-slot sampling parameters with the snapshot-
    upload discipline the paged block tables use: admissions mutate
    the numpy arrays in place, ``device_arrays()`` re-uploads a COPY
    only when dirty (never hand jax a live buffer an in-flight
    transfer could see mutate)."""

    def __init__(self, num_slots):
        S = int(num_slots)
        self.seeds = np.zeros((S,), np.int32)
        self.temps = np.zeros((S,), np.float32)
        self.topks = np.zeros((S,), np.int32)
        self.topps = np.ones((S,), np.float32)
        self._dev = None
        self._dirty = True

    def set_slot(self, slot, req):
        seed, temp, topk, topp = request_sampling_params(req)
        self.seeds[slot] = seed
        self.temps[slot] = temp
        self.topks[slot] = topk
        self.topps[slot] = topp
        self._dirty = True

    def device_arrays(self):
        """(seeds, temps, topks, topps) as device arrays, re-uploaded
        only when an admission dirtied them."""
        import jax.numpy as jnp
        if self._dev is None or self._dirty:
            self._dev = (jnp.asarray(self.seeds.copy()),
                         jnp.asarray(self.temps.copy()),
                         jnp.asarray(self.topks.copy()),
                         jnp.asarray(self.topps.copy()))
            self._dirty = False
        return self._dev
