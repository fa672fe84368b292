"""Operations and bytes a ``deepseek_v3`` decode step needs, from its
shapes alone: the numerators of this architecture's roofline shares.

What the algorithm requires, not what a program happens to execute: a
multiply-add is two operations; an expert's matrices are read once a
step if any token chose it and not at all otherwise; a cached position
is its latent and its rotary key (padding a device layout adds is NOT
counted: a program that moves padded bytes reads as a lower share); the
kernel's per-token operations are those of the (token, expert) pairs
the router made, not of every token through every hit expert.
"""


def attn_params(model):
    """Weights of one layer's attention: W_q, W_kva, W_kvb, W_o (the two
    norms' gains are not matmul weights and are left out, as in the
    published 26,345,472)."""
    h, nh = model["hidden_size"], model["num_attention_heads"]
    r, dn = model["kv_lora_rank"], model["qk_nope_head_dim"]
    dr, dv = model["qk_rope_head_dim"], model["v_head_dim"]
    return (h * nh * (dn + dr) + h * (r + dr) + r * nh * (dn + dv)
            + nh * dv * h)


def expert_params(model):
    """One routed expert: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def shared_params(model):
    return model["n_shared_experts"] * expert_params(model)


def router_params(model):
    return model["hidden_size"] * model["n_routed_experts"]


def dense_mlp_params(model):
    return 3 * model["hidden_size"] * model["intermediate_size"]


def layer_counts(model):
    """(dense layers, expert layers)."""
    n = model["num_hidden_layers"]
    k = min(model["first_k_dense_replace"], n)
    return k, n - k


def total_params(model):
    k, m = layer_counts(model)
    h, v = model["hidden_size"], model["vocab_size"]
    return (k * (attn_params(model) + dense_mlp_params(model))
            + m * (attn_params(model) + shared_params(model)
                   + router_params(model)
                   + model["n_routed_experts"] * expert_params(model))
            + 2 * h * v)


def cache_bytes_per_token_layer(model, itemsize):
    """A cached position in one layer: the latent and the rotary key."""
    return (model["kv_lora_rank"] + model["qk_rope_head_dim"]) * itemsize


def non_expert_weight_bytes(model, itemsize):
    """What a decode step reads whatever the routing: every layer's
    attention, the dense MLPs, the shared experts, the routers and the
    head; of the embedding only the rows looked up (not counted)."""
    k, m = layer_counts(model)
    return itemsize * (
        (k + m) * attn_params(model) + k * dense_mlp_params(model)
        + m * (shared_params(model) + router_params(model))
        + model["hidden_size"] * model["vocab_size"])


def mla_decode_attn_cost(model, positions, itemsize):
    """(operations, bytes) of absorbed attention over ``positions``
    cached positions (summed over the batch) in ONE layer: scores over
    latent and rotary key, then the weighted sum of latents, for every
    head; each position's cache entry read once."""
    nh, r = model["num_attention_heads"], model["kv_lora_rank"]
    dr = model["qk_rope_head_dim"]
    ops = positions * nh * (2 * (r + dr) + 2 * r)
    return ops, positions * cache_bytes_per_token_layer(model, itemsize)


def moe_experts_cost(model, tokens, experts_hit, itemsize):
    """(operations, bytes) of ONE layer's routed experts for a decode
    step of ``tokens`` tokens that hit ``experts_hit`` distinct experts:
    ``tokens * k`` pairs through three matmuls; each hit expert's three
    matrices once, the tokens in and the sum out."""
    h = model["hidden_size"]
    pairs = tokens * model["num_experts_per_tok"]
    ops = 2 * pairs * expert_params(model)
    nbytes = experts_hit * expert_params(model) * itemsize \
        + tokens * h * (itemsize + 4)
    return ops, nbytes


def decode_step_bytes(model, positions, experts_hit_total, itemsize):
    """Bytes one decode step has to read: the weights every step reads,
    the matrices of the experts hit (``experts_hit_total``: summed over
    the expert layers) and the cache of the ``positions`` live
    positions in every layer."""
    return (non_expert_weight_bytes(model, itemsize)
            + experts_hit_total * expert_params(model) * itemsize
            + positions * model["num_hidden_layers"]
            * cache_bytes_per_token_layer(model, itemsize))
