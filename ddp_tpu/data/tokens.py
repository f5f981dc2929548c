"""The program's seeded token data set, for ``--synthetic`` with a token
model: sequences of ids with a learnable signal, in the pair of arrays
the loaders already move (``Dataset(images, labels)``): input rows
``i32[N,T]`` and target rows ``i32[N,T]``, the row shifted by one with
the last position marked :data:`~ddp_tpu.ops.losses.IGNORE`.

The ids are Zipf-distributed over the vocabulary (exponent 1: a few ids
carry most of the mass, as a real vocabulary's do, so routing is uneven),
and each next token is the affine successor ``(31 t + 7) mod V`` of the
current one with probability 0.75, so that the next-token loss falls.
"""
from __future__ import annotations

import numpy as np

from ..ops.losses import IGNORE
from .cifar10 import Dataset

SUCCESSOR_P = 0.75


def synthetic_tokens(n: int, seq_len: int, vocab: int,
                     seed: int = 0) -> Dataset:
    rng = np.random.default_rng([seed, 0x70CE])
    p = 1.0 / np.arange(1, vocab + 1)
    fresh = rng.choice(vocab, size=(n, seq_len), p=p / p.sum())
    follow = rng.random((n, seq_len)) < SUCCESSOR_P
    ids = np.empty((n, seq_len), np.int64)
    ids[:, 0] = fresh[:, 0]
    for t in range(1, seq_len):
        ids[:, t] = np.where(follow[:, t], (31 * ids[:, t - 1] + 7) % vocab,
                             fresh[:, t])
    ids = ids.astype(np.int32)
    targets = np.concatenate(
        [ids[:, 1:], np.full((n, 1), IGNORE, np.int32)], axis=1)
    return Dataset(ids, targets)
