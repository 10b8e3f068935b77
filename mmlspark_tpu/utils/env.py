"""Device/topology discovery — the accelerator-environment glue.

The reference discovers accelerators by shelling out to ``nvidia-smi -L``
(reference: core/env/src/main/scala/EnvironmentUtils.scala:20-50); the
TPU-native equivalent is JAX's device API, which also covers multi-host
process topology (``jax.process_index``) for the distributed backend.
"""

from __future__ import annotations

import functools
from typing import Any, Sequence


def get_devices(backend: str | None = None) -> Sequence[Any]:
    import jax
    return jax.devices(backend) if backend else jax.devices()


def device_count() -> int:
    import jax
    return jax.device_count()


def local_device_count() -> int:
    import jax
    return jax.local_device_count()


def process_index() -> int:
    import jax
    return jax.process_index()


def process_count() -> int:
    import jax
    return jax.process_count()


def device_kind() -> str:
    devs = get_devices()
    return devs[0].device_kind if devs else "none"


def on_tpu() -> bool:
    import jax
    return jax.default_backend() == "tpu"


def tpu_chips_on_host() -> list[str]:
    """Device files of this host's TPU chips, found WITHOUT initialising
    a JAX backend — a supervisor that touched JAX would itself hold the
    chips its workers need (a chip belongs to one process at a time).
    ``/dev/accel*`` is the PCI driver's naming, ``/dev/vfio/<n>`` the
    VFIO one (v5e); empty on a host with no TPU."""
    import glob
    return sorted(glob.glob("/dev/accel[0-9]*")
                  + glob.glob("/dev/vfio/[0-9]*"))


def children_reach_tpu(extra_env: Any = None) -> bool:
    """Would a child process started from here (this process's
    environment overlaid with ``extra_env``, the way every launcher in
    the repo builds a worker's environment) bring up the TPU backend?
    True when the host has TPU chips and the child's ``JAX_PLATFORMS``
    is not pinned to something else (``cpu`` — the explicit way to
    rehearse on virtual devices on a TPU host)."""
    import os
    pinned = (extra_env or {}).get("JAX_PLATFORMS",
                                   os.environ.get("JAX_PLATFORMS", ""))
    platforms = [p.strip() for p in pinned.lower().split(",") if p.strip()]
    if platforms and "tpu" not in platforms:
        return False
    return bool(tpu_chips_on_host())


def default_matmul_dtype():
    """bfloat16 on TPU (MXU-native), float32 elsewhere."""
    import jax.numpy as jnp
    return jnp.bfloat16 if on_tpu() else jnp.float32


def distributed_init(coordinator_address: str | None = None,
                     num_processes: int | None = None,
                     process_id: int | None = None) -> None:
    """Join the multi-host training world.

    The multi-node analog of the reference's hostfile-based MPI launcher
    stub (reference: cntk-train/src/main/scala/CommandBuilders.scala:95-117,
    never wired in): after this call ``jax.devices()`` is global across all
    hosts, so the same Mesh/pjit code spans slices (ICI within a slice, DCN
    between). On TPU pods all arguments are auto-discovered from the
    environment; pass them explicitly for CPU/GPU clusters.

    Arguments left as ``None`` fall back to the ``MMLSPARK_TPU_COORDINATOR``
    / ``MMLSPARK_TPU_NUM_PROCESSES`` / ``MMLSPARK_TPU_PROCESS_ID``
    environment variables, which is how ``mmlspark_tpu.tools.launch`` wires
    the worker processes it spawns; with neither args nor env set, JAX's
    own TPU-pod auto-discovery applies.
    """
    import os

    import jax
    if coordinator_address is None:
        coordinator_address = os.environ.get("MMLSPARK_TPU_COORDINATOR")
    if num_processes is None:
        num_processes = _env_int("MMLSPARK_TPU_NUM_PROCESSES")
    if process_id is None:
        process_id = _env_int("MMLSPARK_TPU_PROCESS_ID")
    kwargs = {}
    if coordinator_address is not None:
        kwargs["coordinator_address"] = coordinator_address
    if num_processes is not None:
        kwargs["num_processes"] = num_processes
    if process_id is not None:
        kwargs["process_id"] = process_id
    jax.distributed.initialize(**kwargs)


def _env_int(name: str) -> int | None:
    import os
    raw = os.environ.get(name)
    return int(raw) if raw not in (None, "") else None


def topology_summary() -> dict[str, Any]:
    """One-call environment report (the GPUCount/nvidia-smi analog)."""
    import jax
    devs = jax.devices()
    return {
        "backend": jax.default_backend(),
        "device_count": len(devs),
        "local_device_count": jax.local_device_count(),
        "process_index": jax.process_index(),
        "process_count": jax.process_count(),
        "device_kind": devs[0].device_kind if devs else "none",
        "platform": devs[0].platform if devs else "none",
    }
