"""How the benchmark's GPT cells meet the program's model class.

The one place that knows ``paddle_tpu.text.models.GPTForCausalLM``'s
attribute names: builds it from a configuration's ``model`` sizes and
writes the benchmark's seeded weights (``benchmarks/weights.py``) into
its parameters.
"""
_BLOCK_LEAVES = {
    "ln1_w": ("ln1", "weight"), "ln1_b": ("ln1", "bias"),
    "qkv_w": ("attn.qkv", "weight"), "qkv_b": ("attn.qkv", "bias"),
    "out_w": ("attn.out", "weight"), "out_b": ("attn.out", "bias"),
    "ln2_w": ("ln2", "weight"), "ln2_b": ("ln2", "bias"),
    "fc1_w": ("mlp.fc1", "weight"), "fc1_b": ("mlp.fc1", "bias"),
    "fc2_w": ("mlp.fc2", "weight"), "fc2_b": ("mlp.fc2", "bias"),
}


def build_model(model):
    """GPTForCausalLM at the configuration's sizes; every option of the
    program that the sizes do not fix stays at the program's default,
    except dropout 0 (a benchmark's loss must repeat)."""
    from paddle_tpu.text.models import GPTForCausalLM, TransformerLMConfig
    cfg = TransformerLMConfig(
        vocab_size=model["vocab_size"], hidden_size=model["hidden_size"],
        num_layers=model["num_hidden_layers"],
        num_heads=model["num_attention_heads"],
        intermediate_size=model["intermediate_size"],
        max_seq_len=model["max_position_embeddings"], dropout=0.0)
    return GPTForCausalLM(cfg)


def _attr(obj, dotted):
    for part in dotted.split("."):
        obj = getattr(obj, part)
    return obj


def param_leaves(net):
    """[(parameter, leaf name, layer or None)] for every parameter."""
    out = [(net.gpt.word_embeddings.weight, "wemb", None),
           (net.gpt.position_embeddings.weight, "pemb", None),
           (net.gpt.ln_f.weight, "lnf_w", None),
           (net.gpt.ln_f.bias, "lnf_b", None)]
    for i, blk in enumerate(net.gpt.blocks):
        for leaf, (mod, attr) in _BLOCK_LEAVES.items():
            out.append((getattr(_attr(blk, mod), attr), leaf, i))
    got, want = {id(p) for p, _, _ in out}, {id(p) for p in net.parameters()}
    if got != want:
        raise RuntimeError("GPTForCausalLM has parameters the benchmark's "
                           "weight layout does not name")
    return out


def set_weights(net, w):
    """Write seeded leaves into the model (keeping each leaf's dtype as
    made: the caller makes them in the type they are used in)."""
    for p, leaf, layer in param_leaves(net):
        a = w[leaf] if layer is None else w[leaf][layer]
        if tuple(a.shape) != tuple(p.value.shape):
            raise RuntimeError(f"{leaf}: seeded shape {a.shape} != "
                               f"program's {p.value.shape}")
        p.value = a
