"""Traffic kind ``token_score_gdn``: ``token_score`` (per-token
log-likelihood of a table of token windows through a language model WITH a
router: the load pass, the routing margins and the clean-token rule are all
its own) for a family whose recurrent state follows the delta rule.

Everything is ``token_score_hybrid``'s, used as it is: beside the shifted
rows and the swapped experts, the recurrent state zeroed every 128 positions
(a chunked recurrence that loses its carry) is planted by the family's
reference. What differs is where set-up stops on a program without the
family: at once, on the import of its module.

Workload file keys: ``token_score``'s.
"""

from __future__ import annotations

from benchmark.drivers import token_score
from benchmark.drivers.token_score_hybrid import (  # noqa: F401 - the API
    FAULTS, check, compare, measure, program_readings, reference_readings,
    release,
)


def setup(ctx) -> dict:
    # a program without the family stops here, at once
    from mmlspark_tpu.models import lm_gdn  # noqa: F401

    return token_score.setup(ctx)
