"""Multi-host bootstrap — the TPU analog of the reference's launcher.

The reference ships ``python -m apex.parallel.multiproc`` which forks
world_size copies of the training script with ``--rank i`` args
(``apex/parallel/multiproc.py:104-127``), predating torch.distributed.launch.

On TPU pods the runtime launches one process per host; what remains is
controller bootstrap. ``initialize_distributed()`` wraps
``jax.distributed.initialize`` with the same env-var conventions the
reference's ecosystem uses (WORLD_SIZE/RANK, reference
``examples/imagenet/main_amp.py:111-123``) mapped to JAX's:

  COORDINATOR_ADDRESS (or MASTER_ADDR:MASTER_PORT)
  NUM_PROCESSES       (or WORLD_SIZE)
  PROCESS_ID          (or RANK)

Running as a module (``python -m apex_tpu.parallel.multiproc script.py``)
spawns NUM_PROCESSES local copies with PROCESS_ID set, logging non-zero
ranks to ``PROC_i.log`` — matching the reference launcher's behavior
(``GPU_i.log``) for local multi-process CPU experiments.  The copies
share one environment, so on a host with TPU chips each would try to
open every chip, and a chip belongs to one process: there the launcher
refuses to start more than one.  One process drives all the chips of a
host (``jax.devices()``, a ``Mesh`` over them).
"""

from __future__ import annotations

import glob
import os
import subprocess
import sys
from typing import Optional


def _local_tpu_chips() -> int:
    """TPU chips on this host, counted from its device nodes without
    touching JAX: a launcher that had opened the chips would itself
    keep them from its children."""
    return (len(glob.glob("/dev/accel[0-9]*"))
            or len(glob.glob("/dev/vfio/[0-9]*")))


def initialize_distributed(coordinator_address: Optional[str] = None,
                           num_processes: Optional[int] = None,
                           process_id: Optional[int] = None) -> int:
    """Initialize JAX multi-host; returns this process's id.

    No-op (returns 0) when single-process (no env and no args).
    """
    import jax

    env = os.environ
    if coordinator_address is None:
        coordinator_address = env.get("COORDINATOR_ADDRESS")
        if coordinator_address is None and "MASTER_ADDR" in env:
            coordinator_address = (f"{env['MASTER_ADDR']}:"
                                   f"{env.get('MASTER_PORT', '12355')}")
    if num_processes is None:
        num_processes = int(env.get("NUM_PROCESSES",
                                    env.get("WORLD_SIZE", "1")))
    if process_id is None:
        process_id = int(env.get("PROCESS_ID", env.get("RANK", "0")))
    if num_processes <= 1:
        return 0
    if coordinator_address is None:
        raise RuntimeError(
            f"NUM_PROCESSES/WORLD_SIZE={num_processes} but no coordinator "
            "address: set COORDINATOR_ADDRESS or MASTER_ADDR(+MASTER_PORT). "
            "Refusing to silently run single-process.")
    jax.distributed.initialize(coordinator_address=coordinator_address,
                               num_processes=num_processes,
                               process_id=process_id)
    return process_id


def main(argv=None):
    argv = argv if argv is not None else sys.argv[1:]
    if not argv:
        print("usage: python -m apex_tpu.parallel.multiproc SCRIPT [args...]",
              file=sys.stderr)
        return 2
    world = int(os.environ.get("NUM_PROCESSES",
                               os.environ.get("WORLD_SIZE", "1")))
    addr = os.environ.get("COORDINATOR_ADDRESS", "localhost:12355")
    chips = (_local_tpu_chips()
             if world > 1 and os.environ.get("JAX_PLATFORMS") != "cpu"
             else 0)
    if chips:
        print(f"apex_tpu.parallel.multiproc: refusing to start {world} "
              f"local processes on a host with {chips} TPU "
              "chip(s): they would share one environment, each would "
              "try to open every chip, and a chip belongs to one "
              "process.  This launcher is for multi-host bootstrap (one "
              "process per host, started by the pod runtime) and for "
              "local CPU runs (JAX_PLATFORMS=cpu); on one host, one "
              "process drives all the chips.", file=sys.stderr)
        return 2
    procs = []
    for rank in range(world):
        env = dict(os.environ, PROCESS_ID=str(rank), NUM_PROCESSES=str(world),
                   COORDINATOR_ADDRESS=addr)
        stdout = None
        if rank != 0:
            stdout = open(f"PROC_{rank}.log", "w")
        procs.append(subprocess.Popen([sys.executable] + argv, env=env,
                                      stdout=stdout,
                                      stderr=subprocess.STDOUT
                                      if stdout else None))
    rc = 0
    for p in procs:
        rc |= p.wait()
    return rc


if __name__ == "__main__":
    sys.exit(main())
