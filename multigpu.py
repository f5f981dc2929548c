"""Data-parallel training over every available chip — the reference
``multigpu.py`` entry point (multigpu.py:254-263), same argv:

    python multigpu.py <total_epochs> <save_every> [--batch_size N]

Where the reference forks one process per GPU (``mp.spawn``,
multigpu.py:262-263) and wires them with an NCCL process group, here one
process per *host* drives all local chips through a ``jax.sharding.Mesh``;
``--batch_size`` stays the per-device batch, so the global batch is
batch_size x num_devices exactly as in DDP.  Multi-host rendezvous (the
MASTER_ADDR/PORT analogue) comes from ``jax.distributed.initialize`` via
DDP_TPU_COORDINATOR/NUM_PROCESSES/PROCESS_ID (ddp_tpu/parallel/dist.py);
``--spawn N`` forks N wired local processes — the reference's ``mp.spawn``
UX — for multi-process drills on a virtual CPU mesh; it refuses on a TPU
host, where one process drives every local chip.
"""
from ddp_tpu.entry import main_multi

if __name__ == "__main__":
    main_multi()  # all devices; same body as the installed ddp-tpu-multi
