"""How the benchmark's ``mimo_v2`` cells meet the program's model class:
the one place that knows ``paddle_tpu.text.mimo_v2``'s names. Builds
``MimoV2ForCausalLM`` at a configuration's sizes around the benchmark's
seeded weights (``benchmarks/weights_mimo_v2.py``) without a second copy
of them, and names what ``serve_arch`` needs of the program.

For whoever adds the next architecture with TOKEN ARRAYS ON SOME LAYERS
AND RINGS ON THE OTHERS (full and window attention mixed:
``CacheSpec(arrays=[(..., layers)], slot=..., ring=W)``; README.md's
table is an accepted file that this PR could not edit). What such an
architecture hands the plane is what one with per-slot state does
(``nemotron_h_program.py``), and nothing of the plane, the pool, the AOT
tool or an accepted reader had to change for it:
``cache_arrays(engine)`` returns EVERY device array the pool holds, the
blocks' and the rings' alike; ``serving_programs`` returns
``spec.with_slots(num_slots)``, so that ``tools/aot_compile_arch.py``
can ask every array's shape, and ``sizing.max_len`` is plain positions
(the ONE block table a slot addresses the full layers' blocks; a ring
needs no table: position ``t`` is entry ``t % W`` of the slot's own
row). ``kv_blocks_peak_pct`` therefore reads what it always read: the
full layers' blocks. ``moe_counts`` is the plane's one hook for program
counters at the window's ends and returns here BOTH the expert counters
(``moe_report``) and what the rings save (``ring_cache_report``:
``cache_live_bytes``, ``cache_full_equiv_bytes``) in one dict; the
accepted expert readers take their keys and leave the rest.
``flops_<arch>.py`` counts a cached position in the FULL layers only and
a slot's ring, read once and one entry written, in every window layer,
whatever the position; padding that a device layout adds is never
counted.
"""
# names the program gives its compiled serving programs (jit_<fn>)
PROGRAMS = {"prefill": "paged_prefill", "decode": "paged_decode"}
# the program's names for its Pallas kernels in the device trace
KERNELS = {"full_attn": "paged_decode_attn",
           "moe_experts": "moe_experts_swiglu_decode"}


def model_config(model, precision):
    from paddle_tpu.text.mimo_v2 import MimoV2Config
    return MimoV2Config.from_hf(model, dtype=precision)


def build_model(model, precision, w):
    """The model class around the seeded leaves ``w`` (adopted, not
    copied: the class checks every shape and dtype against the sizes)."""
    from paddle_tpu.text.mimo_v2 import MimoV2ForCausalLM
    # the weights file's leaf names are the model's parameter paths; a
    # rename on either side is made here
    net = MimoV2ForCausalLM(model_config(model, precision), weights=w)
    net.eval()
    return net


def serving_programs(model, precision, num_slots, block_size, num_blocks,
                     blocks_per_slot):
    """(cache spec, paged_prefill, paged_decode) as the engine builds
    them, from sizes alone (``tools/aot_compile_arch.py``); the spec
    knows the slot count, so that its rings have a shape."""
    from paddle_tpu.serving.paged.mixed_programs import \
        build_paged_mixed_fns
    from paddle_tpu.text.mimo_v2 import mixed_cache_spec
    cfg = model_config(model, precision)
    return (mixed_cache_spec(cfg).with_slots(num_slots),) \
        + build_paged_mixed_fns(cfg, num_slots, block_size, num_blocks,
                                blocks_per_slot)


def cache_arrays(engine):
    """The device arrays the engine's pool holds: the full layers'
    blocks AND the window layers' rings (for the plane's
    ``block_until_ready`` and for freeing them before the reference)."""
    return list(engine.pool.arrays)


def moe_counts(engine):
    """The program's counters the plane reads at both ends of the
    window, in one dict: the expert routing (``{"expert_tokens":
    [layers][experts], "experts_hit": [layers], "layer_steps":
    [layers]}``) and the ring cache's two gauges (``cache_live_bytes``,
    ``cache_full_equiv_bytes``)."""
    out = dict(engine.metrics.moe_report() or {})
    out.update(engine.metrics.ring_cache_report() or {})
    return out or None
