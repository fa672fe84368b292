"""How the benchmark's ``evabyte`` cells meet the program's model class:
the one place that knows ``paddle_tpu.text.evabyte``'s names. Builds
``EvaByteForCausalLM`` at a configuration's sizes around the benchmark's
seeded weights (``benchmarks/weights_evabyte.py``) without a second copy
of them, and names what ``serve_arch`` needs of the program.

For whoever adds the next architecture whose CACHE LENGTH IS NOT ITS
POSITION (``CacheSpec(window=...)``; README.md's table is an accepted
file that this PR could not edit): the pool's table row addresses
ENTRIES, so ``sizing.max_len`` stays the positions a session may reach
and everything that sizes the pool asks the spec (``spec.capacity``).
``tools/aot_compile_arch.py`` sizes a pool from ``max_len`` alone, so it
is run with the entries a slot can hold in ``max_len``'s place,
``aot_compile_arch.py evabyte_6p5b_pp4:3968`` (the programs take their
sizes from the pool, never from ``max_len``); it compiles prefill and
decode, ``tests/test_chip_compile.py`` the compaction program too.
``PROGRAMS`` may name further programs of the step loop (``compact``)
for the readers; ``moe_counts`` is the plane's one hook for program
counters at the window's ends and returns whatever the architecture
counts there: here the entry cache's four numbers
(``ServingMetrics.entry_cache_report``), no expert has any.
``flops_<arch>.py`` counts entries, not positions.
"""
# names the program gives its compiled serving programs (jit_<fn>)
PROGRAMS = {"prefill": "paged_prefill", "decode": "paged_decode",
            "compact": "paged_compact"}
# the program's names for its Pallas kernels in the device trace
KERNELS = {"eva_attn": "paged_decode_attn"}


def model_config(model, precision):
    from paddle_tpu.text.evabyte import EvaByteConfig
    return EvaByteConfig.from_hf(model, dtype=precision)


def build_model(model, precision, w):
    """The model class around the seeded leaves ``w`` (adopted, not
    copied: the class checks every shape and dtype against the sizes)."""
    from paddle_tpu.text.evabyte import EvaByteForCausalLM
    net = EvaByteForCausalLM(model_config(model, precision), weights=w)
    net.eval()
    return net


def serving_programs(model, precision, num_slots, block_size, num_blocks,
                     blocks_per_slot):
    """(cache spec, paged_prefill, paged_decode) as the engine builds
    them, from sizes alone (``tools/aot_compile_arch.py``)."""
    from paddle_tpu.serving.paged.eva_programs import build_paged_eva_fns
    from paddle_tpu.text.evabyte import eva_cache_spec
    cfg = model_config(model, precision)
    return (eva_cache_spec(cfg),) + build_paged_eva_fns(
        cfg, num_slots, block_size, num_blocks, blocks_per_slot)[:2]


def cache_arrays(engine):
    """The device arrays the engine's pool holds (for the plane's
    ``block_until_ready`` and for freeing them before the reference)."""
    return list(engine.pool.arrays)


def moe_counts(engine):
    """The program's counters the plane reads at both ends of the
    window: this architecture has no experts; what it counts is its
    entry cache (``{"entries_live", "positions_live", "compactions",
    "blocks_released"}``)."""
    return engine.metrics.entry_cache_report()
