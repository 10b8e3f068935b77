"""Traffic kind ``table_score``: offline scoring of a table, pass after pass.

``JaxModel(...).transform(table)`` is called back to back on one seeded
``DataTable`` of flat uint8 rows until the window's seconds are up; a call
that has begun is finished, so the window holds whole passes. The
end-to-end metric is ``score_rows_per_s``: all rows whose scores came back
to the host in the window over the window's wall time, the host's feed
(column to matrix, upload, fetch, row list) included.

The folded weights come from the seed (made on the device in one jitted
call by the reference's generator: the stand-in for trained zoo weights)
and are handed to the program as a ``ModelBundle`` around its own module
class. Set-up warms the one compiled shape with a short call whose row
count is not a multiple of the minibatch (the padded tail) and one whole
pass. Of every call, warm-ups included, a sample of rows drawn from the
seed is kept; after the window the plain reference scores the sampled rows
once and ``check`` compares.

Workload file keys read here: ``rows``, ``minibatch_size``,
``warmup_tail_rows``, ``sample_rows_per_call``, ``reference_block_rows``,
``input_col``, ``output_col``, ``limits`` and, optionally, ``jax_model``
(further ``JaxModel`` params).
"""

from __future__ import annotations

import time

import numpy as np


def build_module(cfg: dict):
    """The program's module for the configuration's family and sizes."""
    import jax.numpy as jnp

    if cfg["family"] != "resnet" or cfg["norm"] != "folded":
        raise ValueError("table_score has no module for family "
                         f"{cfg['family']!r} with norm {cfg.get('norm')!r}")
    from mmlspark_tpu.models.resnet import ResNet

    return ResNet(num_classes=cfg["num_classes"],
                  stage_sizes=tuple(cfg["stage_sizes"]), width=cfg["width"],
                  dtype=jnp.dtype(cfg["compute_dtype"]), norm="none",
                  stem=cfg["stem"])


def make_bundle(ctx):
    """The seed's weights in the program's tree: kernels in the storage
    type, biases float32, all made on the device in one call."""
    import jax
    import jax.numpy as jnp
    from flax.traverse_util import unflatten_dict

    from mmlspark_tpu.models.bundle import ModelBundle

    cfg = ctx.config
    ref = ctx.reference()
    store = jnp.dtype(cfg["param_dtype"])

    def build(key):
        flat = ref.make_params(cfg, key)
        return unflatten_dict(
            {k: v.astype(store) if v.ndim >= 2 else v
             for k, v in flat.items()}, sep="/")

    module = build_module(cfg)
    size = cfg["image_size"]
    return ModelBundle(module=module, params=jax.jit(build)(ctx.key()),
                       input_spec=(size, size, cfg["num_channels"]),
                       output_names=type(module).OUTPUT_NAMES,
                       preprocess=cfg["preprocess"], name=cfg["name"])


def make_rows(ctx) -> np.ndarray:
    """``rows`` flat uint8 vectors from the seed, as one matrix. Every row
    is noise at a contrast and a brightness of its own (a right shift by
    1 to 4 bits and an offset that cannot overflow), so that rows differ
    in their answers as images do and an answer in the wrong place shows."""
    cfg, wl = ctx.config, ctx.workload
    n = wl["rows"]
    width = cfg["image_size"] ** 2 * cfg["num_channels"]
    rng = np.random.default_rng(ctx.seed)
    data = rng.integers(0, 256, size=(n, width), dtype=np.uint8)
    shift = rng.integers(1, 5, size=(n, 1), dtype=np.uint8)
    offset = (rng.random((n, 1)) * (255 - (255 >> shift))).astype(np.uint8)
    np.right_shift(data, shift, out=data)
    np.add(data, offset, out=data)
    return data


class Scorer:
    """The system under test with its table, and the sampled answers."""

    def __init__(self, ctx):
        from mmlspark_tpu.data.table import DataTable
        from mmlspark_tpu.models.jax_model import JaxModel

        wl = ctx.workload
        self.wl = wl
        self.data = make_rows(ctx)
        self.table = DataTable({wl["input_col"]: self.data})
        self.model = JaxModel(
            model=make_bundle(ctx), input_col=wl["input_col"],
            output_col=wl["output_col"],
            minibatch_size=wl["minibatch_size"],
            output_node=ctx.config["output_node"],
            **wl.get("jax_model", {}))
        self.rng = np.random.default_rng([ctx.seed, 1])
        self.sampled_rows: list = []     # row index into self.data
        self.sampled_scores: list = []   # the program's answer for it
        self.missing = 0

    def score(self, table, n_rows: int, always: tuple = ()) -> int:
        """One ``transform`` call; keeps a sample of its answers (the
        rows in ``always`` among them). Returns the rows that came back."""
        out = self.model.transform(table)[self.wl["output_col"]]
        got = len(out)
        self.missing += max(n_rows - got, 0)
        k = min(self.wl["sample_rows_per_call"], got)
        picks = set(self.rng.choice(got, size=k, replace=False).tolist())
        picks.update(i for i in always if i < got)
        for i in sorted(picks):
            self.sampled_rows.append(i)
            self.sampled_scores.append(np.array(out[i], np.float32))
        return got


def setup(ctx) -> dict:
    from mmlspark_tpu.data.table import DataTable

    wl = ctx.workload
    t0 = time.perf_counter()
    scorer = Scorer(ctx)
    t1 = time.perf_counter()
    # the padded tail: a short call that is no multiple of the minibatch;
    # this call compiles the one shape (or loads it from the cache)
    short = min(wl["minibatch_size"] + wl["warmup_tail_rows"], wl["rows"])
    scorer.score(DataTable({wl["input_col"]: scorer.data[:short]}), short,
                 always=(0, wl["minibatch_size"] - 1, wl["minibatch_size"],
                         short - 1))
    t2 = time.perf_counter()
    scorer.score(scorer.table, wl["rows"], always=(0, wl["rows"] - 1))
    ctx.say(f"table_score set-up: weights and table {t1 - t0:.2f} s, "
            f"short call {t2 - t1:.2f} s, whole pass "
            f"{time.perf_counter() - t2:.2f} s")
    return {"scorer": scorer}


def measure(ctx, state: dict) -> dict:
    scorer = state["scorer"]
    n = ctx.workload["rows"]
    t0 = time.perf_counter()
    calls = rows = 0
    while time.perf_counter() - t0 < ctx.seconds:
        rows += scorer.score(scorer.table, n)
        calls += 1
    window_s = time.perf_counter() - t0
    return {"window_s": window_s, "attempted": calls * n,
            "failed": calls * n - rows, "calls": calls, "rows": rows,
            "metrics": {"score_rows_per_s": rows / window_s}}


def release(state: dict) -> None:
    """Free the program's device state before the reference runs."""
    scorer = state["scorer"]
    scorer.model = None
    scorer.table = None


def compare(program: np.ndarray, reference: np.ndarray) -> dict:
    """The numbers ``check`` holds to limits: the widest and the
    root-mean-square gap of the logits, each against the same statistic of
    the reference's logits."""
    err = program.astype(np.float64) - reference.astype(np.float64)
    return {"logit_gap_max": float(np.abs(err).max()
                                   / np.abs(reference).max()),
            "logit_gap_rms": float(np.sqrt(np.mean(err ** 2))
                                   / np.sqrt(np.mean(
                                       reference.astype(np.float64) ** 2)))}


# a fault that ``calibrate.py`` plants in the reference put in the program's
# place: every sampled answer handed to the row after it
FAULTS = ("rows_shifted",)


def program_readings(state: dict) -> np.ndarray:
    """The sampled answers of the timed calls, one row each."""
    return np.stack(state["scorer"].sampled_scores)


def reference_readings(ctx, state: dict, quant: str | None = None,
                       fault: str | None = None) -> np.ndarray:
    """Reference logits, one row per sampled answer; ``quant`` computes
    them in a lower precision (the control), ``fault`` plants a fault in
    them (both stand in the program's place, for ``calibrate.py`` and the
    tests)."""
    scorer = state["scorer"]
    ref = ctx.reference()
    unique = sorted(set(scorer.sampled_rows))
    logits = ref.score_rows(ctx.config, ctx.key(), scorer.data[unique],
                            ctx.workload["reference_block_rows"],
                            quant=quant)
    at = {row: i for i, row in enumerate(unique)}
    logits = logits[[at[row] for row in scorer.sampled_rows]]
    return np.roll(logits, 1, axis=0) if fault == "rows_shifted" else logits


def check(ctx, state: dict) -> dict:
    """``name -> (value, limit)`` once the window has closed."""
    scorer = state["scorer"]
    t0 = time.perf_counter()
    reference = reference_readings(ctx, state)
    limits = ctx.workload["limits"]
    program = program_readings(state)
    if program.shape != reference.shape or not np.isfinite(program).all():
        numbers = {"logit_gap_max": float("inf"),
                   "logit_gap_rms": float("inf")}
    else:
        numbers = compare(program, reference)
    numbers["rows_missing"] = float(scorer.missing)
    ctx.say(f"reference {time.perf_counter() - t0:.2f} s over "
            f"{len(set(scorer.sampled_rows))} rows, "
            f"{len(scorer.sampled_rows)} answers compared")
    return {k: (v, limits[k]) for k, v in numbers.items()}
