"""ddp_tpu — TPU-native re-implementation of
``UnchartedWhispers/Distributed-Data-Parallel-Experiment``.

The reference repo is a pair of near-identical PyTorch scripts
(``singlegpu.py`` / ``multigpu.py``, see /root/repo/SURVEY.md) whose only
difference is the data-parallel plumbing (NCCL process group + DDP wrapper +
DistributedSampler).  On TPU that whole diff collapses into the size of a
``jax.sharding.Mesh``: the single-chip and multi-chip paths here are the same
jitted ``train_step``, executed over a mesh of 1 or N devices.

Package layout
--------------
- ``ops/``      low-level NN ops (conv, batch-norm, pooling, linear, losses)
                with PyTorch-default-parity initialisation.
- ``models/``   VGG (reference singlegpu.py:47-82), DeepNN (singlegpu.py:18-44),
                ResNet-18 (BASELINE.json config #3).
- ``optim/``    SGD with the PyTorch momentum/weight-decay convention
                (reference singlegpu.py:135-140) and the triangular LR
                schedule (singlegpu.py:142-149).
- ``data/``     CIFAR-10 pipeline, torch-``DistributedSampler``-exact sharding
                (multigpu.py:147-154), vectorised augmentation, prefetch.
- ``parallel/`` device mesh + shard_map data parallelism (the TPU-native
                replacement for DDP/NCCL, multigpu.py:24-33, 89).
- ``train/``    Trainer engine (singlegpu.py:85-128), evaluation
                (singlegpu.py:184-209), checkpoint save/restore.
- ``serve/``    inference serving: dynamic micro-batcher over bucketed
                AOT-warmed eval forwards, stdlib HTTP front end
                (``python -m ddp_tpu.serve``; no reference analogue).
- ``utils/``    model-size reporting (singlegpu.py:212-225), torch interop
                for parity tests, metrics logging.
"""

__version__ = "0.1.0"
