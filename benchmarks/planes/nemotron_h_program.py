"""How the benchmark's ``nemotron_h`` cells meet the program's model
class: the one place that knows ``paddle_tpu.text.nemotron_h``'s names.
Builds ``NemotronHForCausalLM`` at a configuration's sizes around the
benchmark's seeded weights (``benchmarks/weights_nemotron_h.py``)
without a second copy of them, and names what ``serve_arch`` needs of
the program.

For whoever adds the next architecture WITH PER-SLOT STATE (recurrent
layers: constant size a slot, ``CacheSpec(slot=...)``); README.md's table
is an accepted file and could not be edited by the PR that brought this:
``cache_arrays(engine)`` returns EVERY device array the pool holds,
per-token and per-slot alike; ``serving_programs`` returns
``spec.with_slots(num_slots)``, so that ``tools/aot_compile_arch.py`` can
ask every array's shape; ``flops_<arch>.py`` counts what a step reads
AND writes of the state (``decode_step_bytes`` takes the slot count and
adds every slot's state twice a layer; the state kernel's own ``*_cost``
counts state in + out), never the padding of a device layout, and its
``moe_experts_cost`` counts only the pairs that fall on HELD experts.
"""
# names the program gives its compiled serving programs (jit_<fn>)
PROGRAMS = {"prefill": "paged_prefill", "decode": "paged_decode"}
# the program's names for its Pallas kernels in the device trace
KERNELS = {"ssm_decode": "ssm_decode_step",
           "gqa_attn": "paged_decode_attn",
           "moe_experts": "moe_experts_relu2_decode"}


def model_config(model, precision):
    from paddle_tpu.text.nemotron_h import NemotronHConfig
    return NemotronHConfig.from_hf(model, dtype=precision)


def build_model(model, precision, w):
    """The model class around the seeded leaves ``w`` (adopted, not
    copied: the class checks every shape and dtype against the sizes)."""
    from paddle_tpu.text.nemotron_h import NemotronHForCausalLM
    # the weights file's leaf names are the model's parameter paths; a
    # rename on either side is made here
    net = NemotronHForCausalLM(model_config(model, precision), weights=w)
    net.eval()
    return net


def serving_programs(model, precision, num_slots, block_size, num_blocks,
                     blocks_per_slot):
    """(cache spec, paged_prefill, paged_decode) as the engine builds
    them, from sizes alone (``tools/aot_compile_arch.py``); the spec
    knows the slot count, so that its per-slot arrays have a shape."""
    from paddle_tpu.serving.paged.hybrid_programs import \
        build_paged_hybrid_fns
    from paddle_tpu.text.nemotron_h import hybrid_cache_spec
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
    """The program's expert-routing counters, fetched from the device:
    ``{"expert_tokens": [layers][experts], "experts_hit": [layers],
    "layer_steps": [layers]}`` (None where the program keeps none)."""
    report = getattr(engine.metrics, "moe_report", None)
    return report() if report is not None else None
