"""How the benchmark's ``deepseek_v3`` cells meet the program's model
class: the one place that knows ``paddle_tpu.text.deepseek_v3``'s names.
Builds ``DeepseekV3ForCausalLM`` at a configuration's sizes around the
benchmark's seeded weights (``benchmarks/weights_deepseek_v3.py``)
without a second copy of them, and names what ``serve_arch`` needs of
the program.
"""
# names the program gives its compiled serving programs (jit_<fn>)
PROGRAMS = {"prefill": "paged_prefill", "decode": "paged_decode"}
# the program's names for its Pallas kernels in the device trace
KERNELS = {"mla_decode_attn": "mla_paged_decode_attn",
           "moe_experts": "moe_experts_swiglu_decode"}


def model_config(model, precision):
    from paddle_tpu.text.deepseek_v3 import DeepseekV3Config
    return DeepseekV3Config.from_hf(model, dtype=precision)


def build_model(model, precision, w):
    """The model class around the seeded leaves ``w`` (adopted, not
    copied: the class checks every shape and dtype against the sizes)."""
    from paddle_tpu.text.deepseek_v3 import DeepseekV3ForCausalLM
    # the weights file's leaf names are the model's parameter paths; a
    # rename on either side is made here
    net = DeepseekV3ForCausalLM(model_config(model, precision), weights=w)
    net.eval()
    return net


def serving_programs(model, precision, num_slots, block_size, num_blocks,
                     blocks_per_slot):
    """(cache spec, paged_prefill, paged_decode) as the engine builds
    them, from sizes alone (``tools/aot_compile_arch.py``)."""
    from paddle_tpu.serving.paged.latent_programs import \
        build_paged_latent_fns
    from paddle_tpu.text.deepseek_v3 import latent_cache_spec
    cfg = model_config(model, precision)
    return (latent_cache_spec(cfg),) + build_paged_latent_fns(
        cfg, num_slots, block_size, num_blocks, blocks_per_slot)


def cache_arrays(engine):
    """The device arrays the engine's pool holds (for the plane's
    ``block_until_ready`` and for freeing them before the reference)."""
    return list(engine.pool.arrays)


def moe_counts(engine):
    """The program's expert-routing counters, fetched from the device:
    ``{"expert_tokens": [layers][experts], "experts_hit": [layers],
    "layer_steps": [layers]}`` (None where the program keeps none)."""
    report = getattr(engine.metrics, "moe_report", None)
    return report() if report is not None else None
