"""How the benchmark's ``falcon_h1`` cells meet the program's model
class: the one place that knows ``paddle_tpu.text.falcon_h1``'s names.
Builds ``FalconH1ForCausalLM`` at a configuration's sizes around the
benchmark's seeded weights (``benchmarks/weights_falcon_h1.py``) without
a second copy of them, and names what ``serve_arch`` needs of the
program.

For whoever adds the next architecture WHOSE LAYERS EACH OWN BOTH KINDS
OF CACHE (a state-space mixer and an attention mixer side by side in
every layer; README.md's table is an accepted file that this PR could
not edit). Nothing of the plane, the pool, the AOT tool or an accepted
reader had to change for it: the cache spec is the per-token pair and
the per-slot pair ``nemotron_h`` brought, both with EVERY layer on their
first axis, and the program is ``serving/paged/hybrid_programs.py``'s,
which takes the block from the configuration's class. The kernels keep
the names ``ssm_decode`` and ``gqa_attn``, so ``ssm_decode_roofline``,
``ssm_decode_dev_ms_per_step`` and ``gqa_attn_dev_ms_per_step`` read
this program as they read ``nemotron_h``'s; ``flops_<arch>
.layer_counts`` answers (L, 0, L). ``moe_counts`` is the plane's one
hook for what the program counts at the window's ends and returns here
the cache's two gauges (``serving_state_bytes_per_slot``,
``serving_kv_bytes_per_token``): this program keeps no counter on the
device.
"""
# names the program gives its compiled serving programs (jit_<fn>)
PROGRAMS = {"prefill": "paged_prefill", "decode": "paged_decode"}
# the program's names for its Pallas kernels in the device trace
KERNELS = {"ssm_decode": "ssm_decode_step",
           "gqa_attn": "paged_decode_attn"}
# positions of the sequence the set-up's balance line is read off
_BALANCE_T = 1024


def model_config(model, precision):
    from paddle_tpu.text.falcon_h1 import FalconH1Config
    return FalconH1Config.from_hf(model, dtype=precision)


def log_balance(model, w):
    """One set-up line: the RMS of the residual stream ``h`` going into
    the first and the last layer and of the three terms the layer adds
    (``m``, ``a``, the feed-forward's), from the plain reference over
    one seeded sequence of 1,024 positions: whether the seeded weights
    let every branch reach the served logits
    (``weights_falcon_h1.py``)."""
    import jax.numpy as jnp
    import numpy as np

    from benchmarks import harness
    from benchmarks.reference import falcon_h1 as ref
    T = min(_BALANCE_T, model["max_position_embeddings"])
    ids = np.random.default_rng(17).integers(0, model["vocab_size"], T)
    terms = np.asarray(ref.branch_rms(w, jnp.asarray(ids, jnp.int32),
                                      model))
    for layer in sorted({0, len(terms) - 1}):
        h, m, a, f = (float(x) for x in terms[layer])
        harness.log("branch balance", layer=layer, positions=T, h_rms=h,
                    ssm_rms=m, attn_rms=a, mlp_rms=f,
                    widest_ratio=max(m, a, f) / min(m, a, f))


def build_model(model, precision, w):
    """The model class around the seeded leaves ``w`` (adopted, not
    copied: the class checks every shape and dtype against the sizes)."""
    from paddle_tpu.text.falcon_h1 import FalconH1ForCausalLM
    log_balance(model, w)
    # the weights file's leaf names are the model's parameter paths; a
    # rename on either side is made here
    net = FalconH1ForCausalLM(model_config(model, precision), weights=w)
    net.eval()
    return net


def serving_programs(model, precision, num_slots, block_size, num_blocks,
                     blocks_per_slot):
    """(cache spec, paged_prefill, paged_decode) as the engine builds
    them, from sizes alone (``tools/aot_compile_arch.py``); the spec
    knows the slot count, so that its per-slot arrays have a shape."""
    from paddle_tpu.serving.paged.hybrid_programs import \
        build_paged_hybrid_fns
    from paddle_tpu.text.falcon_h1 import hybrid_cache_spec
    cfg = model_config(model, precision)
    return (hybrid_cache_spec(cfg).with_slots(num_slots),) \
        + build_paged_hybrid_fns(cfg, num_slots, block_size, num_blocks,
                                 blocks_per_slot)


def cache_arrays(engine):
    """The device arrays the engine's pool holds: per-token AND per-slot
    (for the plane's ``block_until_ready`` and for freeing them before
    the reference)."""
    return list(engine.pool.arrays)


def moe_counts(engine):
    """What the engine's gauges ``serving_state_bytes_per_slot`` and
    ``serving_kv_bytes_per_token`` are set from (the cache spec's useful
    bytes); this program keeps no counters on the device."""
    spec = engine.cache_spec
    return {"state_bytes_per_slot": spec.bytes_per_slot,
            "kv_bytes_per_token": spec.bytes_per_token}
