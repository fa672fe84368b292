"""Operations and bytes a ``falcon_h1`` decode step needs, from its
shapes alone: the numerators of this architecture's roofline shares.

What the algorithm requires, not what a program happens to execute: a
multiply-add is two operations; EVERY layer is a state-space layer AND
an attention layer; a cached position is its key and its value in every
layer; a slot's recurrent state is read once and written once a layer,
its convolution window likewise (padding a device layout adds is NOT
counted: a program that moves padded bytes reads as a lower share); the
layers' matrices and the head are read once a step, of the embedding
only the rows looked up (not counted).
"""


def layer_counts(model):
    """(state-space, expert, attention) layers: every layer is both."""
    L = model["num_hidden_layers"]
    return L, 0, L


def _ssm(model):
    H, P = model["mamba_n_heads"], model["mamba_d_head"]
    G, N = model["mamba_n_groups"], model["mamba_d_state"]
    return H, P, G, N, H * P, H * P + 2 * G * N


def mamba_params(model):
    """Matmul weights of one layer's state-space mixer: in_proj and
    out_proj (the convolution's taps, the gains and the per-head scalars
    are not matmul weights)."""
    h = model["hidden_size"]
    H, _, _, _, d, cd = _ssm(model)
    return h * (d + cd + H) + d * h


def attn_params(model):
    h, hd = model["hidden_size"], model["head_dim"]
    nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    return h * nq * hd + 2 * h * nkv * hd + nq * hd * h


def mlp_params(model):
    return 3 * model["hidden_size"] * model["intermediate_size"]


def layer_params(model):
    """Matmul weights of one layer: both mixers and the feed-forward."""
    return mamba_params(model) + attn_params(model) + mlp_params(model)


def total_params(model):
    """Matmul weights held: the layers, the embedding and the head."""
    return model["num_hidden_layers"] * layer_params(model) \
        + 2 * model["hidden_size"] * model["vocab_size"]


def cache_bytes_per_token_layer(model, itemsize):
    """A cached position in one layer: its key and its value over the
    KV heads."""
    return 2 * model["num_key_value_heads"] * model["head_dim"] * itemsize


def state_bytes_per_slot_layer(model, itemsize):
    """What a slot keeps in one layer: the recurrent state in float32
    and the last ``mamba_d_conv - 1`` convolution inputs in the model's
    dtype."""
    H, P, _, N, _, cd = _ssm(model)
    return H * P * N * 4 + (model["mamba_d_conv"] - 1) * cd * itemsize


def weight_bytes_per_step(model, itemsize):
    """What a decode step reads whatever the traffic: every layer's
    matrices and the head."""
    return itemsize * (
        model["num_hidden_layers"] * layer_params(model)
        + model["hidden_size"] * model["vocab_size"])


def ssm_decode_cost(model, slots, itemsize):
    """(operations, bytes) of ONE layer's one-token update of ``slots``
    slots (the kernel ``ssm_decode_step`` and the shift of the
    convolution window around it): the state decayed, the outer product
    added and the state read out (6 operations a state element); the
    state read once and written once in float32, the window likewise,
    the step's inputs (channels in, ``y`` out in float32)."""
    H, P, _, N, d, cd = _ssm(model)
    K = model["mamba_d_conv"]
    state = H * P * N
    ops = slots * (6 * state + 2 * K * cd)
    window = (K - 1) * cd * itemsize
    nbytes = slots * (2 * state * 4 + 2 * window + cd * itemsize + d * 4)
    return ops, nbytes


def gqa_decode_attn_cost(model, positions, itemsize):
    """(operations, bytes) of grouped-query attention over ``positions``
    cached positions (summed over the batch) in ONE layer: scores and
    the weighted sum of values for every QUERY head; each position's key
    and value read once for the whole group of 5."""
    nq, hd = model["num_attention_heads"], model["head_dim"]
    return positions * nq * 4 * hd, \
        positions * cache_bytes_per_token_layer(model, itemsize)


def state_bytes_per_step(model, slots, itemsize):
    """Every slot's state and window, read AND written, in every
    layer."""
    return 2 * slots * model["num_hidden_layers"] \
        * state_bytes_per_slot_layer(model, itemsize)


def cache_bytes_per_step(model, positions, itemsize):
    """The keys and values of the ``positions`` live positions (summed
    over the batch) in every layer."""
    return positions * model["num_hidden_layers"] \
        * cache_bytes_per_token_layer(model, itemsize)


def decode_step_bytes(model, positions, itemsize, slots):
    """Bytes one decode step has to read and write: the weights, every
    slot's state and window in and out, the live keys and values."""
    return (weight_bytes_per_step(model, itemsize)
            + state_bytes_per_step(model, slots, itemsize)
            + cache_bytes_per_step(model, positions, itemsize))
