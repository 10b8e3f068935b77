"""Traffic kind ``token_score``: per-token log-likelihood of a table of
token windows, pass after pass.

``JaxModel(...).transform(table)`` is called back to back on one seeded
``DataTable`` of ``rows`` windows of ``window_tokens`` int32 token ids (drawn
from the seed over the held vocabulary slice, every row different) until
the window's seconds are up; the output column is each window's
``token_logprob``. The end-to-end metric is ``score_rows_per_s`` (rows are
windows), the host's feed included: ``table_score``'s ``measure``, ``release``
and ``compare`` are used as they are.

The weights come from the seed, a leaf at a time (the reference's
generator, made on the device and cast to the program's storage type: the
stand-in for a checkpoint), and are handed to the program as a
``ModelBundle`` around ``models/lm.LatentMoELM``. Set-up warms the one
compiled shape (a short call with a padded tail, one whole pass) and reads
ONCE, through the same ``transform`` with the ``expert_load`` output node,
the picks the table's rows really send to each held expert of each layer:
the table is fixed, so every pass routes alike. Those counts are published
by the program (``moe.tokens``, ``moe.held_pairs``, ``moe.expert_load_max``)
and handed on among the window's counters for the per-layer readers.

How ``correct`` is decided. Of every call a few answers are kept, all from
``sample_rows`` distinct rows fixed by the seed (so that the reference, run
a layer at a time, stays short). The reference gives each token's
log-probability and its **routing margin** (the least, over layers, of the
gap between the last picked and the first unpicked router probability).
Top-k is discrete: where that gap is under bf16 rounding the two sides may
rightly pick differently, so an answer ``out[t]`` is **clean** when the
margin at ``t - 1`` is at least ``clean_margin``, and the compared numbers
are ``logit_gap_max`` / ``logit_gap_rms`` over clean tokens,
``clean_share_short`` (how far the clean share falls under
``clean_share_floor``) and ``rows_missing``.

Workload file keys read here: ``rows``, ``window_tokens``,
``minibatch_size``, ``warmup_tail_rows``, ``sample_rows``,
``sample_rows_per_call``, ``clean_margin``, ``clean_share_floor``,
``input_col``, ``output_col``, ``limits`` and, optionally, ``jax_model``.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.drivers import table_score

# margins at which ``compare`` also reports the gap and the clean share,
# for ``calibrate.py`` to set ``clean_margin`` from
MARGIN_LADDER = (0.0, 1e-4, 3e-4, 1e-3, 3e-3)

# faults ``calibrate.py`` plants in the reference put in the program's
# place: every sampled answer handed to the row after it; the first two
# held experts of every layer trading places
FAULTS = ("rows_shifted", "expert_swapped")

def make_bundle(ctx):
    """The seed's weights in the program's tree (layers stacked on a
    leading axis), each leaf made on the device by the reference's
    generator and cast to the type the program stores it in."""
    import jax
    import jax.numpy as jnp
    from flax.traverse_util import flatten_dict, unflatten_dict

    from mmlspark_tpu.models import lm
    from mmlspark_tpu.models.bundle import ModelBundle

    cfg, ref, key = ctx.config, ctx.reference(), ctx.key()
    module = lm.from_config(cfg)
    window = ctx.workload["window_tokens"]
    want = flatten_dict(jax.eval_shape(
        lambda: module.init(jax.random.PRNGKey(0), jnp.zeros((1, 8))))
        ["params"], sep="/")
    layers = jnp.arange(cfg["num_hidden_layers"])
    flat = {}
    for path, leaf in want.items():
        # leaves with a leading layer axis: the scanned blocks' and the
        # routed experts' stacks (the reference's ``moe/experts/*``)
        per_layer = (path[len("layers/"):] if path.startswith("layers/")
                     else "moe/" + path if path.startswith("experts/")
                     else None)
        if per_layer is not None:
            def make(k, name=per_layer, dtype=leaf.dtype):
                return jax.lax.map(lambda i: ref.layer_leaf(
                    cfg, k, i, name).astype(dtype), layers)
        else:
            def make(k, name=path, dtype=leaf.dtype):
                return ref.outer_leaf(cfg, k, name).astype(dtype)
        flat[path] = jax.jit(make)(key)
        if flat[path].shape != leaf.shape:
            raise ValueError(f"{path}: the reference makes "
                             f"{flat[path].shape}, the program holds "
                             f"{leaf.shape}")
    return ModelBundle(module=module, params=unflatten_dict(flat, sep="/"),
                       input_spec=(window,),
                       output_names=type(module).OUTPUT_NAMES,
                       name=cfg["name"])


def make_rows(ctx) -> np.ndarray:
    """``rows`` windows of ``window_tokens`` ids over the held slice."""
    wl = ctx.workload
    rng = np.random.default_rng(ctx.seed)
    return rng.integers(0, ctx.config["vocab_size"],
                        size=(wl["rows"], wl["window_tokens"]),
                        dtype=np.int32)


class Scorer:
    """The system under test with its table, and the sampled answers
    (the attributes ``table_score.measure`` and ``release`` use)."""

    def __init__(self, ctx):
        from mmlspark_tpu.data.table import DataTable

        wl = ctx.workload
        self.wl = wl
        self.data = make_rows(ctx)
        self.table = DataTable({wl["input_col"]: self.data})
        self.bundle = make_bundle(ctx)
        self.model = self.jax_model(ctx.config["output_node"])
        self.rng = np.random.default_rng([ctx.seed, 1])
        self.pool = sorted(self.rng.choice(
            wl["rows"], size=min(wl["sample_rows"], wl["rows"]),
            replace=False).tolist())
        self.sampled_rows: list = []     # row index into self.data
        self.sampled_scores: list = []   # the program's answer for it
        self.missing = 0

    def jax_model(self, node: str):
        from mmlspark_tpu.models.jax_model import JaxModel

        wl = self.wl
        return JaxModel(model=self.bundle, input_col=wl["input_col"],
                        output_col=wl["output_col"],
                        minibatch_size=wl["minibatch_size"],
                        output_node=node, **wl.get("jax_model", {}))

    def score(self, table, n_rows: int, always: tuple = ()) -> int:
        """One ``transform`` call; keeps a sample of its answers, from the
        pool's rows (and ``always``). Returns the rows that came back."""
        out = self.model.transform(table)[self.wl["output_col"]]
        got = len(out)
        self.missing += max(n_rows - got, 0)
        pool = [i for i in self.pool if i < got]
        k = min(self.wl["sample_rows_per_call"], len(pool))
        picks = set(self.rng.choice(pool, size=k, replace=False).tolist()
                    if k else ())
        picks.update(i for i in always if i < got)
        for i in sorted(picks):
            self.sampled_rows.append(i)
            self.sampled_scores.append(np.array(out[i], np.float32))
        return got

    def expert_load(self) -> np.ndarray:
        """``[layers, held]``: the picks of the whole table on each held
        expert, read from the program's ``expert_load`` node."""
        out = self.jax_model("expert_load").transform(self.table)
        load = np.stack(list(out[self.wl["output_col"]])).sum(axis=0)
        return load.reshape(-1, self.bundle.module.cfg.n_routed_experts)


def fallbacks() -> float:
    """Kernels that gave way to their reference so far, all told."""
    from mmlspark_tpu.obs.metrics import registry
    from mmlspark_tpu.ops.pallas.budget import FALLBACK_COUNTER

    return sum(c.value() for c in registry().series(FALLBACK_COUNTER))


def setup(ctx) -> dict:
    from mmlspark_tpu.data.table import DataTable
    from mmlspark_tpu.models import lm       # a parent without it stops here

    wl = ctx.workload
    t0 = time.perf_counter()
    scorer = Scorer(ctx)
    t1 = time.perf_counter()
    # the padded tail: a short call that is no multiple of the minibatch;
    # this call compiles the one shape (or loads it from the cache)
    short = min(wl["minibatch_size"] + wl["warmup_tail_rows"], wl["rows"])
    pool = scorer.pool
    scorer.score(DataTable({wl["input_col"]: scorer.data[:short]}), short,
                 always=tuple(i for i in pool if i < short)[:1])
    t2 = time.perf_counter()
    scorer.score(scorer.table, wl["rows"], always=(pool[0], pool[-1]))
    t3 = time.perf_counter()
    load = scorer.expert_load()
    layers = load.shape[0]
    tokens = wl["rows"] * wl["window_tokens"]
    moe = lm.publish_expert_load(load, tokens * layers)
    moe["load"] = load.tolist()
    ctx.say(f"token_score set-up: weights and table {t1 - t0:.2f} s, short "
            f"call {t2 - t1:.2f} s, whole pass {t3 - t2:.2f} s, load pass "
            f"{time.perf_counter() - t3:.2f} s; held picks a token a layer "
            f"{moe['moe.held_pairs'] / moe['moe.tokens']:.4f}, busiest "
            f"expert {moe['moe.expert_load_max']} of mean "
            f"{load.mean():.1f}")
    return {"scorer": scorer, "moe": moe, "fallbacks": fallbacks()}


def measure(ctx, state: dict) -> dict:
    window = table_score.measure(ctx, state)
    if fallbacks() != state["fallbacks"] or state["fallbacks"]:
        raise RuntimeError("ops.pallas.vmem_fallback moved: a kernel gave "
                           "way to its reference on the timed path")
    window["moe"] = state["moe"]
    window["window_tokens"] = ctx.workload["window_tokens"]
    return window


def release(state: dict) -> None:
    """Free the program's device state before the reference runs."""
    table_score.release(state)
    state["scorer"].bundle = None


def clean_tokens(margin: np.ndarray, tau: float) -> np.ndarray:
    """``[A, L]`` bool: answer ``t`` is clean when the routing margin of
    the position that produced it, ``t - 1``, is at least ``tau``;
    position 0 (always 0.0 on both sides) is left out."""
    clean = np.zeros(margin.shape, bool)
    clean[:, 1:] = margin[:, :-1] >= tau
    return clean


def compare(program: dict, reference: dict) -> dict:
    """The numbers ``check`` holds to limits. ``reference`` carries the
    margins and the cell's ``clean_margin`` / ``clean_share_floor``;
    the same numbers at every margin of ``MARGIN_LADDER`` follow, for
    ``calibrate.py``."""
    got, want = program["logprob"], reference["logprob"]
    if got.shape != want.shape or not np.isfinite(got).all():
        return {"logit_gap_max": float("inf"), "logit_gap_rms": float("inf"),
                "clean_share_short": float("inf")}

    def at(tau):
        clean = clean_tokens(reference["margin"], tau)
        share = float(clean[:, 1:].mean())
        if not clean.any():
            return {"logit_gap_max": float("inf"),
                    "logit_gap_rms": float("inf")}, share
        return table_score.compare(got[clean], want[clean]), share

    out, share = at(reference["clean_margin"])
    out["clean_share_short"] = max(0.0,
                                   reference["clean_share_floor"] - share)
    for tau in MARGIN_LADDER:
        gaps, share = at(tau)
        out[f"gap_max_at_{tau:g}"] = gaps["logit_gap_max"]
        out[f"gap_rms_at_{tau:g}"] = gaps["logit_gap_rms"]
        out[f"clean_share_at_{tau:g}"] = share
    return out


def program_readings(state: dict) -> dict:
    """The sampled answers of the timed calls, one row each."""
    return {"logprob": np.stack(state["scorer"].sampled_scores)}


def reference_readings(ctx, state: dict, quant: str | None = None,
                       fault: str | None = None) -> dict:
    """Reference log-probabilities and routing margins, one row per
    sampled answer; ``quant`` computes them in a lower precision (the
    control), ``fault`` plants a fault (both stand in the program's place,
    for ``calibrate.py`` and the tests)."""
    scorer = state["scorer"]
    wl = ctx.workload
    unique = sorted(set(scorer.sampled_rows))
    inner = fault if fault in ctx.reference().FAULTS else None
    done = state.setdefault("reference_done", {})
    if (quant, inner) not in done:           # one run serves a shifted copy
        done[quant, inner] = ctx.reference().score_rows(
            ctx.config, ctx.key(), scorer.data[unique], quant=quant,
            fault=inner)
    logprob, margin = done[quant, inner]
    at = {row: i for i, row in enumerate(unique)}
    index = [at[row] for row in scorer.sampled_rows]
    logprob, margin = logprob[index], margin[index]
    if fault == "rows_shifted":
        logprob = np.roll(logprob, 1, axis=0)
    return {"logprob": logprob, "margin": margin,
            "clean_margin": wl["clean_margin"],
            "clean_share_floor": wl["clean_share_floor"]}


def check(ctx, state: dict) -> dict:
    """``name -> (value, limit)`` once the window has closed."""
    scorer = state["scorer"]
    t0 = time.perf_counter()
    reference = reference_readings(ctx, state)
    limits = ctx.workload["limits"]
    numbers = compare(program_readings(state), reference)
    numbers["rows_missing"] = float(scorer.missing)
    ctx.say(f"reference {time.perf_counter() - t0:.2f} s over "
            f"{len(set(scorer.sampled_rows))} rows, "
            f"{len(scorer.sampled_rows)} answers compared; "
            + ", ".join(f"{k} {v:.4g}" for k, v in numbers.items()
                        if "_at_" in k))
    return {k: (numbers[k], limits[k]) for k in limits}
