"""Generate a format-identical fake ``cifar-10-batches-py`` archive.

The real acceptance artifact — final accuracy after a 20-epoch CIFAR-10
run (/root/reference/singlegpu.py:248-249) — needs the real 163 MB
dataset, which an egress-less host cannot fetch.
This generator produces an archive that is byte-layout-identical to what
``torchvision.datasets.CIFAR10(download=True)`` leaves on disk (the layout
``ddp_tpu.data.cifar10.load`` parses, reference singlegpu.py:161-171):

- ``cifar-10-batches-py/data_batch_{1..5}`` + ``test_batch``
- each a pickled dict with **bytes** keys (the real files were pickled
  under Python 2; loading them with ``encoding="bytes"`` yields bytes
  keys, so faking str keys would MISS the real code path) —
  ``b"data"``: uint8 ``[N, 3072]`` in CHW raster order, ``b"labels"``:
  list of ints, plus the cosmetic ``b"batch_label"``/``b"filenames"``
- ``batches.meta`` with ``b"label_names"``

Pixels carry the same learnable mean-brightness signal as
``cifar10.synthetic`` (optionally with baked-in label noise for the
non-saturated acceptance regime, or ``--random`` for pure noise), so the
full-scale dress rehearsal exercises the real 6-file parse -> NHWC
transpose -> resident upload -> 20-epoch path AND shows real learning.

Usage: python tests/make_fake_cifar.py <root> [--per_batch 10000]
           [--test_count 10000] [--seed 0] [--label_noise 0.0] [--random]
"""
from __future__ import annotations

import argparse
import os
import pickle

import numpy as np

NUM_CLASSES = 10
_BATCH_DIR = "cifar-10-batches-py"


def _make_split(rng: np.random.Generator, noise_rng: np.random.Generator,
                n: int, *, label_noise: float, random_pixels: bool):
    labels = rng.integers(0, NUM_CLASSES, n).astype(np.int64)
    if random_pixels:
        imgs = rng.integers(0, 256, (n, 3, 32, 32)).astype(np.uint8)
    else:
        # The synthetic() signal (data/cifar10.py): label encoded in mean
        # brightness — generated in CHW order since that is the on-disk
        # raster (the NHWC transpose belongs to the loader under test).
        base = rng.integers(0, 64, (n, 3, 32, 32))
        imgs = np.clip(base + labels[:, None, None, None] * 18,
                       0, 255).astype(np.uint8)
    if label_noise > 0.0:
        flip = noise_rng.random(n) < label_noise
        labels = np.where(flip, noise_rng.integers(0, NUM_CLASSES, n),
                          labels)
    return imgs.reshape(n, 3072), labels


def _write_batch(path: str, name: str, imgs: np.ndarray,
                 labels: np.ndarray) -> None:
    d = {
        b"batch_label": name.encode(),
        b"labels": [int(l) for l in labels],
        b"data": imgs,
        b"filenames": [b"fake_%05d.png" % i for i in range(len(labels))],
    }
    with open(path, "wb") as f:
        pickle.dump(d, f)


def generate(root: str, *, per_batch: int = 10000, test_count: int = 10000,
             seed: int = 0, label_noise: float = 0.0,
             random_pixels: bool = False) -> str:
    """Write the archive under ``root``; returns the batch-dir path."""
    base = os.path.join(root, _BATCH_DIR)
    os.makedirs(base, exist_ok=True)
    rng = np.random.default_rng(seed)
    noise_rng = np.random.default_rng([seed, 0x5EED_10])
    for i in range(1, 6):
        imgs, labels = _make_split(rng, noise_rng, per_batch,
                                   label_noise=label_noise,
                                   random_pixels=random_pixels)
        _write_batch(os.path.join(base, f"data_batch_{i}"),
                     f"training batch {i} of 5", imgs, labels)
    imgs, labels = _make_split(rng, noise_rng, test_count,
                               label_noise=label_noise,
                               random_pixels=random_pixels)
    _write_batch(os.path.join(base, "test_batch"), "testing batch 1 of 1",
                 imgs, labels)
    with open(os.path.join(base, "batches.meta"), "wb") as f:
        pickle.dump({b"label_names": [b"class_%d" % c
                                      for c in range(NUM_CLASSES)],
                     b"num_cases_per_batch": per_batch,
                     b"num_vis": 3072}, f)
    return base


def main() -> None:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("root", help="Dataset root (the CLI's --data_root; the "
                                "archive dir is created inside it)")
    p.add_argument("--per_batch", type=int, default=10000,
                   help="Rows per data_batch_N file (real: 10000)")
    p.add_argument("--test_count", type=int, default=10000)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--label_noise", type=float, default=0.0,
                   help="Bake this label-flip fraction into the archive "
                        "(non-saturated acceptance regime; analytic "
                        "ceiling 1 - 0.9*p)")
    p.add_argument("--random", action="store_true",
                   help="Pure random pixels (no learnable signal)")
    args = p.parse_args()
    base = generate(args.root, per_batch=args.per_batch,
                    test_count=args.test_count, seed=args.seed,
                    label_noise=args.label_noise,
                    random_pixels=args.random)
    n_bytes = sum(os.path.getsize(os.path.join(base, f))
                  for f in os.listdir(base))
    print(f"wrote {base} ({5 * args.per_batch} train / {args.test_count} "
          f"test rows, {n_bytes / 2**20:.1f} MiB)")


if __name__ == "__main__":
    main()
