"""The generator of token training data: parameters in, arrays out.

A job mix's ``data`` block gives the sizes and the rule: ``n_train``
sequences of ``seq_len`` ids over a vocabulary of ``vocab`` rows (the
configuration's slice), one document a sequence, no packing.  Ids are
Zipf-distributed (``zipf_exponent``: a few ids carry most of the mass, as
a real vocabulary's do, so that routing is uneven), and each next token
is the affine successor ``(a t + c) mod vocab`` of the current one
(``successor: [a, c]``) with probability ``successor_p``, so that the
next-token loss of a real model falls.  The targets are the row shifted
by one, the last position marked ``IGNORE``.

Only ``--seed`` decides the data, and the same seed gives the same data.
The rule is the program's ``ddp_tpu.data.tokens``'s, written again here
so that the yardstick does not move with the program.
"""
from __future__ import annotations

import numpy as np

IGNORE = -1


def zipf_successor(n: int, seq_len: int, vocab: int, seed: int, *,
                   exponent: float = 1.0, successor_p: float = 0.75,
                   successor=(31, 7)):
    """``(ids int32 [n, seq_len], targets int32 [n, seq_len])``."""
    rng = np.random.default_rng([seed, 0x70CE])
    p = np.arange(1, vocab + 1, dtype=np.float64) ** -exponent
    fresh = rng.choice(vocab, size=(n, seq_len), p=p / p.sum())
    follow = rng.random((n, seq_len)) < successor_p
    a, c = successor
    ids = np.empty((n, seq_len), np.int64)
    ids[:, 0] = fresh[:, 0]
    for t in range(1, seq_len):
        ids[:, t] = np.where(follow[:, t], (a * ids[:, t - 1] + c) % vocab,
                             fresh[:, t])
    ids = ids.astype(np.int32)
    targets = np.concatenate(
        [ids[:, 1:], np.full((n, 1), IGNORE, np.int32)], axis=1)
    return ids, targets


def make(data: dict, vocab: int, seed: int):
    """Arrays for a mix's ``data`` block (``stands_for`` is for the
    reader)."""
    return zipf_successor(
        int(data["n_train"]), int(data["seq_len"]), vocab, seed,
        exponent=float(data["zipf_exponent"]),
        successor_p=float(data["successor_p"]),
        successor=tuple(data["successor"]))
