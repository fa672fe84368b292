"""Operations and bytes an ``ouro`` decode step needs, from its shapes
alone: the numerators of this architecture's roofline shares.

What the algorithm requires, not what a program happens to execute (the
counting rules of ``flops_nemotron_h.py``): a multiply-add is two
operations. The model is LOOPED: one stack of ``num_hidden_layers``
layers run ``total_ut_steps`` times over the same weights. So

  * the layers' weights are read once a PASS: ``total_ut_steps`` times a
    step. No program can read them less often: the stack (4.9 GB at the
    published sizes) does not stay on the chip between passes, and pass
    ``r + 1`` of a layer needs pass ``r`` of every later layer first.
    The head is read once; of the embedding only the rows looked up
    (not counted);
  * a cached position is a key and a value in every pass of every
    layer: ``total_ut_steps x num_hidden_layers`` (k, v) pairs;
  * the attention kernel is called that many times a step; each call
    reads its entry's live positions once, writes the new position's key
    and value (one row a slot of each: the TILES a device layout makes
    of them are padding and not counted, PERF.md open question 27d),
    takes the queries in and hands the outputs out.
"""


def passes(model):
    return model["total_ut_steps"]


def cache_layers(model):
    """(k, v) pairs a cached position owns."""
    return passes(model) * model["num_hidden_layers"]


def layer_params(model):
    """One layer: q, k, v, o; gate, up, down; four norms."""
    h, hd = model["hidden_size"], model["head_dim"]
    nq, nkv = model["num_attention_heads"], model["num_key_value_heads"]
    return (h * (nq + 2 * nkv) * hd + nq * hd * h
            + 3 * h * model["intermediate_size"] + 4 * h)


def total_params(model):
    """Every parameter: the one stack, the embedding, the head, the
    final norm and the exit gate with its bias."""
    h = model["hidden_size"]
    return (model["num_hidden_layers"] * layer_params(model)
            + 2 * h * model["vocab_size"] + 2 * h + 1)


def cache_bytes_per_token(model, itemsize):
    return cache_layers(model) * 2 * model["num_key_value_heads"] \
        * model["head_dim"] * itemsize


def weight_bytes_per_step(model, itemsize):
    """What a decode step reads whatever the batch: the stack once a
    pass, the head, the final norm and the gate once a pass."""
    h = model["hidden_size"]
    return itemsize * (
        passes(model) * (model["num_hidden_layers"] * layer_params(model)
                         + 2 * h)
        + h * model["vocab_size"])


def loop_attn_cost(model, positions, slots, itemsize):
    """(operations, bytes) of ONE call of the attention kernel (one pass
    of one layer) over ``positions`` cached positions (summed over the
    batch): scores and the weighted sum for every query head; each
    position's key and value read once; the new position's key and value
    written; the queries in and the outputs out."""
    nq, nkv, hd = (model["num_attention_heads"],
                   model["num_key_value_heads"], model["head_dim"])
    return positions * nq * 4 * hd, \
        (positions + slots) * 2 * nkv * hd * itemsize \
        + slots * 2 * nq * hd * itemsize


def decode_step_bytes(model, positions, itemsize, slots):
    """Bytes one decode step has to read and write: the weights (the
    stack once a pass), and every call of the attention kernel's (the
    live positions' keys and values in every pass of every layer, the
    new entries written, q and o)."""
    return weight_bytes_per_step(model, itemsize) + cache_layers(model) \
        * loop_attn_cost(model, positions, slots, itemsize)[1]
