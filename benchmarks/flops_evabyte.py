"""Operations and bytes an ``evabyte`` decode step needs, from its shapes
alone: the numerators of this architecture's roofline shares.

What the algorithm requires, not what a program happens to execute: a
multiply-add is two operations; every layer's matrices are read once a
step; a cache ENTRY (a position of the current window, or the pooled pair
of a chunk of a window that is over) is its key and its value over all
heads, read once a step by every layer; a compaction reads a window's raw
entries once and writes its summaries once, in every layer. Padding a
device layout adds is NOT counted: a program that moves padded bytes
reads as a lower share.
"""


def head_dim(model):
    return model["hidden_size"] // model["num_attention_heads"]


def summaries_per_window(model):
    return model["window_size"] // model["chunk_size"]


def entries(model, positions):
    """Cache entries a sequence holds once ``positions`` positions are
    in it (also the entry position ``positions`` is written at)."""
    W = model["window_size"]
    return (positions // W) * summaries_per_window(model) + positions % W


def layer_params(model):
    """One layer: four attention projections, the three feed-forward
    matrices, two norm gains and the two pooling vectors a head."""
    h, f = model["hidden_size"], model["intermediate_size"]
    return 4 * h * h + 3 * h * f + 4 * h


def total_params(model):
    h, v = model["hidden_size"], model["vocab_size"]
    return (model["num_hidden_layers"] * layer_params(model)
            + v * h + h + model.get("num_pred_heads", 1) * v * h)


def step_weight_bytes(model, itemsize):
    """What a decode step reads whatever the traffic: every layer whole,
    the final norm and prediction head 0 (the one that is sampled); of
    the embedding only the rows looked up (not counted)."""
    h, v = model["hidden_size"], model["vocab_size"]
    return itemsize * (model["num_hidden_layers"] * layer_params(model)
                       + h + v * h)


def entry_bytes_per_layer(model, itemsize):
    """One cache entry in one layer: a key and a value over all heads."""
    return 2 * model["hidden_size"] * itemsize


def attn_decode_cost(model, live_entries, itemsize):
    """(operations, bytes) of ONE layer's decode attention over
    ``live_entries`` entries (summed over the batch): scores and the
    weighted sum for every head; each entry's key and value once."""
    return live_entries * 4 * model["hidden_size"], \
        live_entries * entry_bytes_per_layer(model, itemsize)


def compact_cost(model, itemsize):
    """(operations, bytes) of ONE window's compaction in ALL layers: two
    scores, two weights and two weighted sums a raw entry (about 6
    operations a lane); the window's raw entries read once, its
    summaries written once."""
    W, L = model["window_size"], model["num_hidden_layers"]
    per = entry_bytes_per_layer(model, itemsize)
    return L * W * 6 * model["hidden_size"], \
        L * (W + summaries_per_window(model)) * per


def decode_step_bytes(model, live_entries, itemsize,
                      compactions_per_step=0.0):
    """Bytes one decode step has to move: the weights, every live
    entry's key and value in every layer, and the step's share of the
    compactions (windows that ended a step, a fraction)."""
    return (step_weight_bytes(model, itemsize)
            + model["num_hidden_layers"] * live_entries
            * entry_bytes_per_layer(model, itemsize)
            + compactions_per_step * compact_cost(model, itemsize)[1])
