"""Device-resident dataset: the whole training set lives in HBM.

The reference streams every batch host->device (`.to(gpu_id)` per batch,
multigpu.py:105-106).  For CIFAR-10 that traffic is pointless on TPU: the
full uint8 training set is ~150 MB — under 1% of a chip's HBM — so we
upload it once, replicated over the mesh, and each step *gathers* its batch
by index on device (train/epoch.py).  Per-epoch host->device traffic drops
from ~150 MB of images to a ~200 KB int32 index matrix, and the input
pipeline stops existing as a bottleneck (SURVEY.md §7 hard-part #4).

The images go up in the row gather's layout (ops/gather.py
:class:`~ddp_tpu.ops.gather.RowTable`): ``u8[N, D/128, 128]`` where a
row's element count ``D`` is a multiple of 128 (CIFAR's 3,072 is), the
rows as they are where it is not.  The reshape is a free view on the host
and a copy of every byte on the device, so it happens here, once, and no
train or evaluation program ever has the whole table as a result.

Augmentation correspondingly moves on device (data/device_augment.py) —
the same RandomCrop+HFlip distribution as the host path (torchvision
transforms, singlegpu.py:154-160).

Sampler semantics are unchanged: the index matrix is produced by the same
``DistributedSampler``-exact host samplers (data/sampler.py), so device r
sees exactly rank r's reference data stream.
"""
from __future__ import annotations

from typing import Optional

import jax
import numpy as np

from jax.sharding import Mesh

from ..ops.gather import RowTable
from ..parallel.mesh import replicated_sharding
from .cifar10 import Dataset

# Fraction of a device's HBM the replicated dataset may occupy.  The rest
# is headroom for params/momentum/activations and XLA scratch — CIFAR-scale
# data (~150 MB vs ~16 GB HBM) never comes near it; the guard exists so a
# too-large dataset fails with instructions instead of a raw XLA OOM
# mid-upload (the reference's streaming loop, multigpu.py:104-107, has no
# such cliff and the superset must not add one).
HBM_BUDGET_FRACTION = 0.8


def _device_bytes_limit(device) -> Optional[int]:
    """Per-device memory capacity in bytes, or None when the backend does
    not report one (the CPU backend; tests monkeypatch this seam)."""
    try:
        stats = device.memory_stats()
    except Exception:  # backend without memory_stats support
        return None
    return (stats or {}).get("bytes_limit")


class ResidentData:
    """``dataset.images``/``labels`` as replicated device arrays:
    ``images`` a :class:`~ddp_tpu.ops.gather.RowTable` (the device array
    is ``images.data``, a sample's shape ``images.row_shape``), ``labels``
    int32 ``[N]``.

    uint8 images on device; the ToTensor u8/255 scaling happens inside the
    train step (train/step.py ``_as_input``), so HBM holds the dataset at
    1/4 fp32 size.  Multi-host: every process passes its (identical) host
    copy and the replicated global array is assembled process-locally.

    Raises :class:`ValueError` before any upload when the dataset would not
    fit the per-device HBM budget — resident mode replicates the FULL
    dataset on every device, so capacity does not grow with the mesh; the
    streaming loader is the mode for datasets beyond HBM.
    """

    def __init__(self, dataset: Dataset, mesh: Mesh):
        rep = replicated_sharding(mesh)
        images = RowTable.from_rows(np.ascontiguousarray(dataset.images))
        labels = np.ascontiguousarray(dataset.labels, dtype=np.int32)
        # Probe an ADDRESSABLE device: under multi-host, mesh device 0
        # belongs to process 0 only, and a non-addressable device's
        # memory_stats raises.  The guard must make the SAME decision on
        # every process (a rank that raises while others proceed leaves
        # the others hanging in the assembly collective), so multi-host
        # runs agree on the global minimum limit — with "no limit
        # reported anywhere" disabling the guard everywhere.  (A process
        # owning NO mesh devices is unsupported throughout — it gets
        # assemble_from_local's explicit error below.)
        from ..parallel.mesh import local_replica_ids
        local = [mesh.devices.flat[i] for i in local_replica_ids(mesh)]
        limit = _device_bytes_limit(local[0]) if local else None
        if jax.process_count() > 1:
            # Mesh-based global min (NOT multihost_utils.process_allgather,
            # which assumes equal per-host device counts and breaks on
            # asymmetric topologies); "no limit reported" anywhere
            # disables the guard everywhere.
            from ..parallel.mesh import process_min_mib
            limit = process_min_mib(mesh, limit)
        needed = images.data.nbytes + labels.nbytes
        if limit is not None and needed > HBM_BUDGET_FRACTION * limit:
            raise ValueError(
                f"resident mode replicates the whole dataset into every "
                f"device's HBM, but this dataset is "
                f"{needed / 2**20:,.0f} MiB and the per-device budget is "
                f"{HBM_BUDGET_FRACTION * limit / 2**20:,.0f} MiB "
                f"({HBM_BUDGET_FRACTION:.0%} of {limit / 2**20:,.0f} MiB "
                f"HBM, the rest reserved for params/activations). "
                f"Drop --resident to stream batches from the host "
                f"(optionally with --device_augment), or shrink the "
                f"dataset.")
        if jax.process_count() == 1:
            self.images, self.labels = jax.device_put((images, labels), rep)
        else:
            # Explicit global shapes (= local: fully replicated), so the
            # upload works on asymmetric host->device topologies too.
            self.images, self.labels = jax.tree_util.tree_map(
                lambda a: jax.make_array_from_process_local_data(
                    rep, a, a.shape), (images, labels))
