"""Operations and bytes a ``nemotron_h`` decode step needs, from its
shapes alone: the numerators of this architecture's roofline shares.

What the algorithm requires, not what a program happens to execute: a
multiply-add is two operations; an expert's matrices are read once a
step if any token chose it and not at all otherwise; a cached position
is its key and its value in the attention layers ONLY; a slot's
recurrent state is read once and written once a state-space layer, its
convolution window likewise (padding a device layout adds is NOT
counted: a program that moves padded bytes reads as a lower share); the
expert kernel's per-token operations are those of the (token, expert)
pairs that fall on the HELD experts, not of every token through every
hit expert.
"""


def layer_counts(model):
    """(state-space, expert, attention) layers."""
    p = model["hybrid_override_pattern"]
    return p.count("M"), p.count("E"), p.count("*")


def _ssm(model):
    H, P = model["mamba_num_heads"], model["mamba_head_dim"]
    G, N = model["n_groups"], model["ssm_state_size"]
    return H, P, G, N, H * P, H * P + 2 * G * N


def mamba_params(model):
    """Matmul weights of one state-space layer: in_proj and out_proj
    (the convolution's taps, the gains and the per-head scalars are not
    matmul weights; their bytes are counted in ``ssm_decode_cost``)."""
    h = model["hidden_size"]
    H, _, _, _, d, cd = _ssm(model)
    return h * (d + cd + H) + d * h


def attn_params(model):
    h, hd = model["hidden_size"], model["head_dim"]
    nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    return h * nq * hd + 2 * h * nkv * hd + nq * hd * h


def expert_params(model):
    """One routed expert: up and down."""
    return 2 * model["hidden_size"] * model["moe_intermediate_size"]


def shared_params(model):
    return 2 * model["hidden_size"] \
        * model["moe_shared_expert_intermediate_size"]


def router_experts(model):
    return model.get("router_experts", model["n_routed_experts"])


def router_params(model):
    return model["hidden_size"] * router_experts(model)


def total_params(model):
    """Parameters HELD: ``n_routed_experts`` counts the held experts."""
    m, e, a = layer_counts(model)
    return (m * mamba_params(model) + a * attn_params(model)
            + e * (shared_params(model) + router_params(model)
                   + model["n_routed_experts"] * expert_params(model))
            + 2 * model["hidden_size"] * model["vocab_size"])


def cache_bytes_per_token_layer(model, itemsize):
    """A cached position in one ATTENTION layer: its key and its value
    over the KV heads."""
    return 2 * model["num_key_value_heads"] * model["head_dim"] * itemsize


def state_bytes_per_slot_layer(model, itemsize):
    """What a slot keeps in one STATE-SPACE layer: the recurrent state
    in float32 and the last ``conv_kernel - 1`` convolution inputs in
    the model's dtype."""
    H, P, _, N, _, cd = _ssm(model)
    return H * P * N * 4 + (model["conv_kernel"] - 1) * cd * itemsize


def non_expert_weight_bytes(model, itemsize):
    """What a decode step reads whatever the routing: every state-space
    and attention layer's projections, the shared experts, the routers
    and the head; of the embedding only the rows looked up (not
    counted)."""
    m, e, a = layer_counts(model)
    return itemsize * (
        m * mamba_params(model) + a * attn_params(model)
        + e * (shared_params(model) + router_params(model))
        + model["hidden_size"] * model["vocab_size"])


def ssm_decode_cost(model, slots, itemsize):
    """(operations, bytes) of ONE state-space layer's one-token update
    of ``slots`` slots (the kernel ``ssm_decode_step`` and the shift of
    the convolution window around it): the state decayed, the outer
    product added and the state read out (6 operations a state element);
    the state read once and written once in float32, the window
    likewise, the step's inputs (channels in, ``y`` out in float32)."""
    H, P, _, N, d, cd = _ssm(model)
    state = H * P * N
    ops = slots * (6 * state + 2 * model["conv_kernel"] * cd)
    window = (model["conv_kernel"] - 1) * cd * itemsize
    nbytes = slots * (2 * state * 4 + 2 * window + cd * itemsize + d * 4)
    return ops, nbytes


def gqa_decode_attn_cost(model, positions, itemsize):
    """(operations, bytes) of grouped-query attention over ``positions``
    cached positions (summed over the batch) in ONE attention layer:
    scores and the weighted sum of values for every QUERY head; each
    position's key and value read once for the whole group."""
    nq, hd = model["num_attention_heads"], model["head_dim"]
    return positions * nq * 4 * hd, \
        positions * cache_bytes_per_token_layer(model, itemsize)


def moe_experts_cost(model, tokens, experts_hit, itemsize):
    """(operations, bytes) of ONE layer's HELD routed experts for a
    decode step of ``tokens`` tokens that hit ``experts_hit`` distinct
    held experts: of the ``tokens * k`` pairs the held share (held over
    the router's experts, in expectation) through two matmuls; each hit
    expert's two matrices once, the tokens in and the sum out."""
    h = model["hidden_size"]
    pairs = tokens * model["num_experts_per_tok"] \
        * model["n_routed_experts"] / router_experts(model)
    ops = 2 * pairs * expert_params(model)
    nbytes = experts_hit * expert_params(model) * itemsize \
        + tokens * h * (itemsize + 4)
    return ops, nbytes


def decode_step_bytes(model, positions, experts_hit_total, itemsize,
                      slots):
    """Bytes one decode step has to read and write: the weights every
    step reads, the matrices of the experts hit (``experts_hit_total``:
    summed over the expert layers), the keys and values of the
    ``positions`` live positions in every ATTENTION layer, and every
    slot's state and window, read AND written, in every STATE-SPACE
    layer."""
    m, _, a = layer_counts(model)
    return (non_expert_weight_bytes(model, itemsize)
            + experts_hit_total * expert_params(model) * itemsize
            + positions * a * cache_bytes_per_token_layer(model, itemsize)
            + 2 * slots * m * state_bytes_per_slot_layer(model, itemsize))

