"""Operations and bytes a ``mimo_v2`` decode step needs, from its shapes
alone: the numerators of this architecture's roofline shares.

What the algorithm requires, not what a program happens to execute (the
counting rules of ``flops_nemotron_h.py``): a multiply-add is two
operations; an expert's three matrices are read once a step if any
token chose it and not at all otherwise; a cached position is its key
(``head_dim`` lanes, however a pool splits them) and its value
(``v_head_dim``) in the FULL attention layers ONLY; a WINDOW layer reads
a slot's ring of ``sliding_window`` entries once and writes one entry,
whatever the position (padding a device layout adds is NOT counted: a
program that moves padded bytes, or a whole ring to write one entry,
reads as a lower share); the expert kernel's per-token operations are
those of the (token, expert) pairs that fall on the HELD experts.
"""


def layer_counts(model):
    """(full attention, window attention, dense FFN, expert FFN)
    layers."""
    a, f = model["hybrid_layer_pattern"], model["moe_layer_freq"]
    return (sum(1 for x in a if not x), sum(1 for x in a if x),
            sum(1 for x in f if not x), sum(1 for x in f if x))


def kv_heads(model, window):
    return model.get("swa_num_key_value_heads",
                     model["num_key_value_heads"]) if window \
        else model["num_key_value_heads"]


def attn_params(model, window):
    h, nq = model["hidden_size"], model["num_attention_heads"]
    hd, dv, nkv = model["head_dim"], model["v_head_dim"], \
        kv_heads(model, window)
    return h * nq * hd + h * nkv * (hd + dv) + nq * dv * h


def dense_params(model):
    return 3 * model["hidden_size"] * model["intermediate_size"]


def expert_params(model):
    """One routed expert: gate, up and down."""
    return 3 * model["hidden_size"] * model["moe_intermediate_size"]


def router_experts(model):
    return model.get("router_experts", model["n_routed_experts"])


def router_params(model):
    return model["hidden_size"] * router_experts(model)


def total_params(model):
    """Parameters HELD: ``n_routed_experts`` counts the held experts."""
    lf, lw, d, e = layer_counts(model)
    return (lf * attn_params(model, False) + lw * attn_params(model, True)
            + d * dense_params(model)
            + e * (router_params(model)
                   + model["n_routed_experts"] * expert_params(model))
            + 2 * model["hidden_size"] * model["vocab_size"])


def cache_bytes_per_token_layer(model, itemsize):
    """A cached position in one FULL layer: its key and its value over
    the full layers' KV heads."""
    return kv_heads(model, False) * (model["head_dim"]
                                     + model["v_head_dim"]) * itemsize


def ring_bytes_per_slot_layer(model, itemsize):
    """What a slot keeps in one WINDOW layer: ``sliding_window`` keys
    and values over the window layers' KV heads."""
    return model["sliding_window"] * kv_heads(model, True) * (
        model["head_dim"] + model["v_head_dim"]) * itemsize


def non_expert_weight_bytes(model, itemsize):
    """What a decode step reads whatever the routing: every attention
    layer's projections, the dense layers, the routers and the head; of
    the embedding only the rows looked up (not counted)."""
    lf, lw, d, e = layer_counts(model)
    return itemsize * (
        lf * attn_params(model, False) + lw * attn_params(model, True)
        + d * dense_params(model) + e * router_params(model)
        + model["hidden_size"] * model["vocab_size"])


def _q_and_o_bytes(model, slots, itemsize):
    return slots * model["num_attention_heads"] * (
        model["head_dim"] + model["v_head_dim"]) * itemsize


def full_attn_cost(model, positions, slots, itemsize):
    """(operations, bytes) of ONE full layer's decode attention over
    ``positions`` cached positions (summed over the batch): scores and
    the weighted sum of values for every QUERY head; each position's key
    and value read once for the whole group; the queries in and the
    outputs out."""
    nq = model["num_attention_heads"]
    return positions * nq * 2 * (model["head_dim"] + model["v_head_dim"]), \
        positions * cache_bytes_per_token_layer(model, itemsize) \
        + _q_and_o_bytes(model, slots, itemsize)


def window_attn_cost(model, slots, itemsize):
    """(operations, bytes) of ONE window layer's decode attention for
    ``slots`` slots: every slot's ring read once, one entry of it
    written, the queries in and the outputs out."""
    nq, W = model["num_attention_heads"], model["sliding_window"]
    ring = ring_bytes_per_slot_layer(model, itemsize)
    return slots * W * nq * 2 * (model["head_dim"] + model["v_head_dim"]), \
        slots * (ring + ring // W) + _q_and_o_bytes(model, slots, itemsize)


def moe_experts_cost(model, tokens, experts_hit, itemsize):
    """(operations, bytes) of ONE layer's HELD routed experts for a
    decode step of ``tokens`` tokens that hit ``experts_hit`` distinct
    held experts: of the ``tokens * k`` pairs the held share (held over
    the router's experts, in expectation) through three matmuls; each
    hit expert's three matrices once, the tokens in and the sum out."""
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
    ``positions`` live positions in every FULL layer, and every slot's
    ring (read once, one entry written) in every WINDOW layer."""
    lf, lw, _, _ = layer_counts(model)
    ring = ring_bytes_per_slot_layer(model, itemsize)
    return (non_expert_weight_bytes(model, itemsize)
            + experts_hit_total * expert_params(model) * itemsize
            + positions * lf * cache_bytes_per_token_layer(model, itemsize)
            + slots * lw * (ring + ring // model["sliding_window"]))
