"""Operations and bytes a GPT step needs, from its shapes alone.

These are the numerators of every roofline share and of ``train_mfu_pct``:
what the algorithm requires, not what a program happens to execute
(recomputation, padding to a bucket, logits for positions nobody reads
and copies of the KV cache are NOT counted). A multiply-add is two
operations. Causal attention over a sequence of T tokens needs T*(T+1)/2
query-key pairs.
"""


def _sizes(model):
    return (model["hidden_size"], model["num_hidden_layers"],
            model["intermediate_size"], model["vocab_size"])


def layer_matmul_params(model):
    """Weights of one block's four matmuls (qkv, out, fc1, fc2)."""
    h, _, f, _ = _sizes(model)
    return 4 * h * h + 2 * h * f


def forward_ops(model, tokens, attended_pairs, head_tokens):
    """Forward pass: ``tokens`` positions through every block's matmuls,
    ``attended_pairs`` query-key pairs of attention (QK^T and PV, each 2
    operations per pair per hidden unit), and the vocabulary head on
    ``head_tokens`` positions."""
    h, n, _, v = _sizes(model)
    return (2 * n * layer_matmul_params(model) * tokens
            + 4 * n * h * attended_pairs
            + 2 * h * v * head_tokens)


def causal_pairs(length, start=0):
    """Query-key pairs when positions start..length-1 attend causally
    over 0..length-1."""
    return length * (length + 1) // 2 - start * (start + 1) // 2


def prefill_ops(model, prompt_len):
    """One prompt, whole: only the last position needs the head."""
    return forward_ops(model, prompt_len, causal_pairs(prompt_len), 1)


def decode_ops(model, context_lens):
    """One decode step over sequences whose caches hold
    ``context_lens`` tokens (the new token included)."""
    return forward_ops(model, len(context_lens), sum(context_lens),
                       len(context_lens))


def train_ops_per_token(model, seq_len):
    """Forward plus backward (twice the forward) of next-token training
    on sequences of ``seq_len``; the optimizer's elementwise work is not
    counted, nor is any recomputation."""
    fwd = forward_ops(model, seq_len, causal_pairs(seq_len), seq_len)
    return 3 * fwd / seq_len


def flash_attention_ops(batch, heads, seq_len, head_dim, backward):
    """Causal attention kernel alone: forward is QK^T and PV; backward
    recomputes nothing it is charged for and needs dQ, dK, dV and dP
    (five matmuls of the same size against the forward's two)."""
    pairs = batch * heads * causal_pairs(seq_len)
    fwd = 4 * pairs * head_dim
    return fwd * (2.5 if backward else 1.0)


def weight_bytes_per_decode_step(model, bytes_per_weight):
    """Every block's weights and the head are read once per step; of
    the embedding tables only the rows looked up, which is negligible
    and not counted."""
    h, n, f, v = _sizes(model)
    per_layer = layer_matmul_params(model) + 9 * h + f  # + biases, norms
    return (n * per_layer + h * v + 2 * h) * bytes_per_weight


def kv_bytes(model, tokens, bytes_per_value):
    """Keys and values of ``tokens`` cached positions, all layers."""
    h, n, _, _ = _sizes(model)
    return 2 * n * h * tokens * bytes_per_value
