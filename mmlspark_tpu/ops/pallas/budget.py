"""The VMEM budget every kernel wrapper sizes its tiles against, and the
one way a miss becomes visible."""

from __future__ import annotations

from mmlspark_tpu.core.logging_utils import get_logger
from mmlspark_tpu.obs.metrics import registry as _obs_registry

_log = get_logger(__name__)

# per-program bytes a wrapper may plan for: under the 16 MiB scoped-VMEM
# default of a TPU core, with room for the compiler's own temporaries
VMEM_BUDGET = 14 * 2 ** 20

FALLBACK_COUNTER = "ops.pallas.vmem_fallback"


def lane_pad(n: int) -> int:
    """``n`` rounded up to the 128-wide lane tile VMEM arrays pad to."""
    return -(-n // 128) * 128


def note_vmem_fallback(kernel: str, shape: tuple) -> None:
    """Record that ``kernel`` gave way to its XLA reference because
    ``shape`` does not fit VMEM: one warning at trace time and the
    always-live ``ops.pallas.vmem_fallback{kernel=...}`` counter (what
    ``chip_smoke.py`` asserts stays zero)."""
    _log.warning("%s: tile %s exceeds the %d MiB VMEM budget — running "
                 "the XLA reference instead of the kernel", kernel,
                 tuple(shape), VMEM_BUDGET >> 20)
    _obs_registry().counter(FALLBACK_COUNTER, kernel=kernel).add()
