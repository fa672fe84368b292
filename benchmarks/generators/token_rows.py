"""Seeded token rows for next-token training.

Row i is ``seq_len + 1`` token ids drawn from (seed, i) alone, so the
reference regenerates any batch without the program's loader. The input
is the row without its last token, the labels the row without its first.
"""
import numpy as np


def row(seed, i, seq_len, token_id_limit):
    rng = np.random.default_rng([abs(int(seed)), int(i)])
    return rng.integers(0, token_id_limit, size=seq_len + 1,
                        dtype=np.int64)


def batch(seed, k, batch_size, seq_len, token_id_limit):
    """Batch k of a sequential, unshuffled pass: (inputs, labels)."""
    rows = np.stack([row(seed, k * batch_size + j, seq_len,
                         token_id_limit) for j in range(batch_size)])
    return rows[:, :-1], rows[:, 1:]


def build(params, seed, seq_len, token_id_limit):
    """A map-style dataset for ``paddle.io.DataLoader``."""
    from paddle_tpu.io import Dataset

    class TokenRows(Dataset):
        def __len__(self):
            return int(params.get("rows", 1 << 24))

        def __getitem__(self, i):
            r = row(seed, i, seq_len, token_id_limit)
            return r[:-1], r[1:]

    return TokenRows()
