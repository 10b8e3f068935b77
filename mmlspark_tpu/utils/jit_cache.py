"""Where XLA's persistent compilation cache lives.

Every process that compiles for the device calls
:func:`place_compilation_cache` before its first compile: ``chip_smoke.py``,
``bench.py``, ``tools/serve.py``, the fleet backend worker and the train
service worker. The placement comes from OUTSIDE the code:

* ``JAX_COMPILATION_CACHE_DIR`` set — jax reads it itself
  (``jax.config.jax_compilation_cache_dir``) and this helper sets
  nothing, so whoever runs the program decides where compiled programs
  survive between runs;
* unset — one fixed, git-ignored directory inside the checkout
  (``<checkout>/.jax_cache``). Fixed on purpose: a root made from a temp
  dir, a pid or a timestamp is a cache no later process ever finds.

Wherever the cache lives, every compiled program is written to it: jax's
default skips programs that compiled in under a second, and a restarting
process re-pays hundreds of those (one v5e smoke run makes ~465 compile
requests, 14 of them over a second — and the decode program of a 4-layer
model is not among the 14). ``JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS``
in the environment overrides this, like the directory.

This is jax's own cache (every jitted program: the train step, the serve
ladder, the decode program). The repo's AOT cache of serialized plan
segments (``core/compile_cache.py``) is a separate store with its own
explicit placement (``compile_cache=`` / ``MMLSPARK_TPU_COMPILE_CACHE``).
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
MIN_COMPILE_TIME_ENV_VAR = "JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"

#: the in-checkout default, next to the package directory
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def place_compilation_cache() -> str:
    """Make sure jax's persistent compilation cache has a home; returns
    the directory. Importing jax here does not initialise a backend."""
    import jax

    # every process that compiles for the device comes through here
    # before its first compile: where the always-on compile tier's
    # listeners go in when obs was imported before jax (a no-op after)
    from mmlspark_tpu.obs import compile_tier
    compile_tier.register()
    if not os.environ.get(MIN_COMPILE_TIME_ENV_VAR):
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    placed = os.environ.get(ENV_VAR)
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
