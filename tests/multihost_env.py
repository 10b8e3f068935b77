"""Shared preamble for the multi-host worker scripts.

Importing this module — BEFORE importing jax — pins the worker onto the
virtual-CPU simulation platform (env fallbacks for hand runs; the launcher
presets them) and puts the repo root on sys.path. Kept in one place so the
platform-pinning workaround cannot silently diverge between workers.
"""

import json
import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=2"
                               ).strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def pin_platform() -> None:
    """Pin the platform through the config as well as the env var, so a
    jax that something imported before the var was set still obeys it."""
    import jax
    jax.config.update("jax_platforms", os.environ["JAX_PLATFORMS"])


def params_checksum(params) -> float:
    import jax
    import numpy as np
    leaves = jax.tree_util.tree_leaves(params)
    return float(sum(float(np.asarray(l).sum()) for l in leaves))


def write_result(pid: int, result: dict, prefix: str = "out") -> None:
    """One JSON result file per rank under $MULTIHOST_OUT_DIR + stdout."""
    out_dir = os.environ.get("MULTIHOST_OUT_DIR")
    if out_dir:
        with open(os.path.join(out_dir, f"{prefix}_{pid}.json"), "w") as f:
            json.dump(result, f)
    print(json.dumps(result), flush=True)
