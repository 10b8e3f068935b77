"""Traffic kind ``token_score_plain``: per-token log-likelihood of a table of
token windows, pass after pass, for a language model WITHOUT a router.

The traffic, the weights from the seed, the sampled answers and the
fallback check are ``token_score``'s (its ``Scorer``, ``make_rows``,
``make_bundle``, ``fallbacks``, ``program_readings`` and ``release`` are used
as they are). What differs follows from there being no discrete pick in the
model: set-up makes no load pass and publishes no ``moe.*`` counts, and
``correct`` compares EVERY answer from position 1 on (position 0 is 0.0 on
both sides) with ``table_score.compare``: ``logit_gap_max``, ``logit_gap_rms``
and ``rows_missing``; there is no routing margin, so no ``clean_*`` key.

Workload file keys read here: ``rows``, ``window_tokens``,
``minibatch_size``, ``warmup_tail_rows``, ``sample_rows``,
``sample_rows_per_call``, ``input_col``, ``output_col``, ``limits`` and,
optionally, ``jax_model``.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.drivers import table_score
from benchmark.drivers.token_score import (  # noqa: F401 - the driver's API
    Scorer, fallbacks, make_bundle, make_rows, program_readings, release,
)

# faults ``calibrate.py`` plants in the reference put in the program's
# place: every sampled answer handed to the row after it; the recurrent
# state zeroed every 256 positions (a chunked scan that loses its carry)
FAULTS = ("rows_shifted", "state_dropped")


def setup(ctx) -> dict:
    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.models import lm_ssm  # noqa: F401 - a parent without
    #                                         the model stops here

    wl = ctx.workload
    t0 = time.perf_counter()
    scorer = Scorer(ctx)
    t1 = time.perf_counter()
    # a short call first: it compiles the one shape (or loads it from the
    # cache); then one whole pass
    short = min(wl["minibatch_size"] + wl["warmup_tail_rows"], wl["rows"])
    pool = scorer.pool
    scorer.score(DataTable({wl["input_col"]: scorer.data[:short]}), short,
                 always=tuple(i for i in pool if i < short)[:1])
    t2 = time.perf_counter()
    scorer.score(scorer.table, wl["rows"], always=(pool[0], pool[-1]))
    ctx.say(f"token_score_plain set-up: weights and table {t1 - t0:.2f} s, "
            f"short call {t2 - t1:.2f} s, whole pass "
            f"{time.perf_counter() - t2:.2f} s")
    return {"scorer": scorer, "fallbacks": fallbacks()}


def measure(ctx, state: dict) -> dict:
    window = table_score.measure(ctx, state)
    if fallbacks() != state["fallbacks"] or state["fallbacks"]:
        raise RuntimeError("ops.pallas.vmem_fallback moved: a kernel gave "
                           "way to its reference on the timed path")
    window["window_tokens"] = ctx.workload["window_tokens"]
    return window


def reference_readings(ctx, state: dict, quant: str | None = None,
                       fault: str | None = None) -> dict:
    """Reference log-probabilities, one row per sampled answer; ``quant``
    computes them in a lower precision (the control), ``fault`` plants a
    fault (both stand in the program's place, for ``calibrate.py`` and the
    tests)."""
    scorer = state["scorer"]
    unique = sorted(set(scorer.sampled_rows))
    inner = fault if fault in ctx.reference().FAULTS else None
    done = state.setdefault("reference_done", {})
    if (quant, inner) not in done:           # one run serves a shifted copy
        done[quant, inner] = ctx.reference().score_rows(
            ctx.config, ctx.key(), scorer.data[unique], quant=quant,
            fault=inner)
    at = {row: i for i, row in enumerate(unique)}
    logprob = done[quant, inner][[at[row] for row in scorer.sampled_rows]]
    if fault == "rows_shifted":
        logprob = np.roll(logprob, 1, axis=0)
    return {"logprob": logprob}


def compare(program: dict, reference: dict) -> dict:
    """The numbers ``check`` holds to limits, over every answer from
    position 1 on."""
    got, want = program["logprob"], reference["logprob"]
    if got.shape != want.shape or not np.isfinite(got).all():
        return {"logit_gap_max": float("inf"), "logit_gap_rms": float("inf")}
    return table_score.compare(got[:, 1:], want[:, 1:])


def check(ctx, state: dict) -> dict:
    """``name -> (value, limit)`` once the window has closed."""
    scorer = state["scorer"]
    t0 = time.perf_counter()
    numbers = compare(program_readings(state),
                      reference_readings(ctx, state))
    numbers["rows_missing"] = float(scorer.missing)
    ctx.say(f"reference {time.perf_counter() - t0:.2f} s over "
            f"{len(set(scorer.sampled_rows))} rows, "
            f"{len(scorer.sampled_rows)} answers compared")
    limits = ctx.workload["limits"]
    return {k: (numbers[k], limits[k]) for k in limits}
