"""How the benchmark's ``ouro`` cells meet the program's model class:
the one place that knows ``paddle_tpu.text.ouro``'s names. Builds
``OuroForCausalLM`` at a configuration's sizes around the benchmark's
seeded weights (``benchmarks/weights_ouro.py``) without a second copy of
them, and names what ``serve_arch`` needs of the program.

For whoever adds the next architecture whose CACHE LAYERS ARE NOT ITS
WEIGHT LAYERS (a stack run several times over the same weights, every
pass with keys and values of its own: ``CacheSpec(num_layers = passes x
layers, ...)``; README.md's table is an accepted file that this PR
could not edit). Nothing of the plane, the pool, the AOT tool or an
accepted reader had to change for it: the pool's arrays have ``passes x
layers`` on their first axis and ONE block table a slot addresses them
all, so ``kv_blocks_peak_pct`` reads what it always read;
``spec.bytes_per_token`` is already the bytes of every pass. What such
an architecture must get right is in ``flops_<arch>.py``: a decode step
reads the layers' weights once a PASS (no program can read them less
often: a stack of several GB does not stay on the chip between passes),
a cached position is ``passes x layers`` (k, v) pairs, and the attention
kernel is called ``passes x layers`` times a step. ``moe_counts`` is the
plane's one hook for program counters at the window's ends and returns
here the loop's counters (``ServingMetrics.loop_report``), or None from
a program that keeps none.
"""
# names the program gives its compiled serving programs (jit_<fn>)
PROGRAMS = {"prefill": "paged_prefill", "decode": "paged_decode"}
# the program's names for its Pallas kernels in the device trace
KERNELS = {"loop_attn": "paged_decode_attn"}


def model_config(model, precision):
    from paddle_tpu.text.ouro import OuroConfig
    return OuroConfig.from_hf(model, dtype=precision)


def build_model(model, precision, w):
    """The model class around the seeded leaves ``w`` (adopted, not
    copied: the class checks every shape and dtype against the sizes)."""
    from paddle_tpu.text.ouro import OuroForCausalLM
    # the weights file's leaf names are the model's parameter paths; a
    # rename on either side is made here
    net = OuroForCausalLM(model_config(model, precision), weights=w)
    net.eval()
    return net


def serving_programs(model, precision, num_slots, block_size, num_blocks,
                     blocks_per_slot):
    """(cache spec, paged_prefill, paged_decode) as the engine builds
    them, from sizes alone (``tools/aot_compile_arch.py``)."""
    from paddle_tpu.serving.paged.looped_programs import \
        build_paged_looped_fns
    from paddle_tpu.text.ouro import looped_cache_spec
    cfg = model_config(model, precision)
    return (looped_cache_spec(cfg),) + build_paged_looped_fns(
        cfg, num_slots, block_size, num_blocks, blocks_per_slot)


def cache_arrays(engine):
    """The device arrays the engine's pool holds (for the plane's
    ``block_until_ready`` and for freeing them before the reference)."""
    return list(engine.pool.arrays)


def moe_counts(engine):
    """The loop's counters the plane reads at both ends of the window:
    ``{"passes", "cache_passes", "exit_pass": [passes], "passes_run",
    "gate_mass": [passes]}``."""
    report = getattr(engine.metrics, "loop_report", None)
    return report() if report is not None else None
