"""Traffic kind ``token_score_hybrid``: ``token_score`` (per-token
log-likelihood of a table of token windows through a language model WITH a
router: the load pass, the routing margins and the clean-token rule are all
its own) for a family that also carries a recurrent state.

Everything is ``token_score``'s, used as it is; what differs is the list of
faults ``calibrate.py`` plants: beside the shifted rows and the swapped
experts, the recurrent state zeroed every 128 positions (a chunked scan that
loses its carry), which the family's reference knows how to plant
(``reference_readings`` hands any fault the reference lists to it).

Workload file keys: ``token_score``'s.
"""

from __future__ import annotations

from benchmark.drivers import token_score
from benchmark.drivers.token_score import (  # noqa: F401 - the driver's API
    check, compare, measure, program_readings, reference_readings, release,
)

FAULTS = token_score.FAULTS + ("state_dropped",)


def setup(ctx) -> dict:
    # a parent without the family stops here, at once
    from mmlspark_tpu.models import lm_mamba2  # noqa: F401

    return token_score.setup(ctx)
