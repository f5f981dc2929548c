"""The one generator of training data: parameters in, arrays out.

A job mix's ``data`` block gives the sizes.  The rule is the program's
``ddp_tpu.data.synthetic``'s (a label in 0..9 drawn per image, pixel noise
in 0..63, the label added to every pixel as 18 grey levels, so that the
loss of a real model falls), rewritten so that a table of a million
images is made in seconds: the program's version draws int64 noise for
every pixel (25 KB of host memory an image).  Here one block of 65,536
noise images is drawn, and block ``k`` of the table is that block with
its rows rolled by ``k`` pixels plus each image's own label lift, filled
by a few threads.  The stream of random numbers is not the program's:
only ``--seed`` decides the data, and the same seed gives the same data.
"""
from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor

import numpy as np

CHUNK = 65536
THREADS = 4


def brightness_label(n: int, seed: int, shape=(32, 32, 3), classes: int = 10,
                     noise_levels: int = 64, step: int = 18):
    """``(images uint8 [n, *shape], labels int32 [n])``."""
    if noise_levels - 1 + (classes - 1) * step > 255:
        raise ValueError("noise_levels + classes * step overflows uint8")
    rng = np.random.default_rng([seed, 0xDA7A])
    labels = rng.integers(0, classes, n, dtype=np.int32)
    row = int(np.prod(shape))
    noise = rng.integers(0, noise_levels, (min(n, CHUNK), row),
                         dtype=np.uint8)
    lift = (labels * step).astype(np.uint8)[:, None]
    images = np.empty((n, row), np.uint8)

    def fill(k: int) -> None:
        lo, hi = k * CHUNK, min((k + 1) * CHUNK, n)
        np.add(np.roll(noise[:hi - lo], k, axis=1), lift[lo:hi],
               out=images[lo:hi])

    with ThreadPoolExecutor(THREADS) as pool:
        list(pool.map(fill, range(-(-n // CHUNK))))
    return images.reshape(n, *shape), labels


def make(data: dict, seed: int):
    """Arrays for a mix's ``data`` block: ``n_train`` images
    (``stands_for`` is for the reader)."""
    return brightness_label(int(data["n_train"]), seed)
