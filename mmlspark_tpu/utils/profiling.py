"""Profiler hooks: device-level traces to complement the Timer stage.

The reference's observability is wall-clock logging (Timer stage,
pipeline-stages/src/main/scala/Timer.scala:54-123 — mirrored by
stages/utility.Timer); on TPU the interesting time is *inside* the
compiled program, so these helpers expose the JAX/XLA profiler:

    from mmlspark_tpu.utils.profiling import trace, annotate

    with trace("/tmp/profile"):            # viewable in XProf/Perfetto
        with annotate("score-batch"):
            model.transform(table)

Traces capture per-op device timelines (MXU occupancy, HBM stalls, ICI
collectives) — only the process that holds the chip can trace it.
"""

from __future__ import annotations

import contextlib
import os
from typing import Any, Iterator


@contextlib.contextmanager
def trace(log_dir: str, create_perfetto_link: bool = False) -> Iterator[str]:
    """Capture a device trace for the enclosed block into ``log_dir``."""
    import jax

    os.makedirs(log_dir, exist_ok=True)
    with jax.profiler.trace(log_dir,
                            create_perfetto_link=create_perfetto_link):
        yield log_dir


def annotate(name: str) -> Any:
    """Named span inside a trace (shows on the host timeline and groups
    the device ops dispatched under it)."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def start_server(port: int = 9999) -> Any:
    """Live profiling endpoint for XProf's capture button."""
    import jax
    return jax.profiler.start_server(port)
