"""Traffic kind ``pipeline_score``: featurizing a table of ENCODED images
through a fitted pipeline, pass after pass.

``PipelineModel([ImageTransformer().resize(S, S), UnrollImage(),
ImageFeaturizer(...)]).transform(table)`` is called back to back on one
seeded ``DataTable`` whose image column holds JPEG ``bytes``, one object a
row, until the window's seconds are up. Every pass pays what the
reference library's headline user pays: the host decodes each row
(``data/readers.decode_image``, through ``ImageTransformer``, which takes a
bytes column) and resizes it, the rows, now separate allocations, are
stacked and uploaded, the device unrolls and runs the configuration's
network cut to its pooled features, and every column a stage wrote comes
back (the unrolled pixels among them). The end-to-end metric is
``score_rows_per_s``, all of that included: ``table_score``'s ``measure``,
``release`` and ``compare`` are used as they are, and the weights are its
``make_bundle``'s (the seed's, the same the reference is given).

The images are seeded smooth noise (low-resolution noise enlarged, each
row at a contrast and a brightness of its own, so that rows differ in their
answers as photographs do), ``source_size`` square, encoded once in set-up.

How ``correct`` is decided. Of every call a sample of feature rows is kept.
After the window the sampled rows are decoded and resized once more, by
nothing of the program's (:func:`plain_pixels`: OpenCV's decoder and the
resize written out from its definition), and the plain reference
(``reference/<family>.py``) computes their pooled features: its forward
with an identity in the head's place, which at ``highest`` precision hands
the pooled vector through to the bit. So a wrong decode, channel order or
resize in the program's first stage shows in the same two numbers as a
wrong network (the planted fault ``channels_swapped`` is the proof).

No cell of ``BENCHMARK.json`` uses this driver yet: the cell it was written
for, ``resnet50_featurize.pipeline``, is queued in ``PERF.md`` section 7
with its parameters and with what its admission waits on; its tiny cell
under ``benchmark/tests`` runs it by hand.

Workload file keys read here: ``rows``, ``source_size``, ``jpeg_quality``,
``minibatch_size``, ``warmup_tail_rows``, ``sample_rows_per_call``,
``reference_block_rows``, ``input_col``, ``unrolled_col``, ``output_col``,
``limits``.
"""

from __future__ import annotations

import time

import numpy as np

from benchmark.drivers import table_score
from benchmark.drivers.table_score import (  # noqa: F401 - the driver's API
    compare, measure, program_readings, release,
)

# faults that ``calibrate.py`` plants in the reference put in the program's
# place: ``table_score``'s, and the decoded pixels read red first
FAULTS = table_score.FAULTS + ("channels_swapped",)


def make_rows(ctx) -> list:
    """``rows`` JPEG-encoded images from the seed, each its own ``bytes``."""
    from concurrent.futures import ThreadPoolExecutor

    import cv2

    wl = ctx.workload
    n, size = wl["rows"], wl["source_size"]
    channels = ctx.config["num_channels"]
    rng = np.random.default_rng(ctx.seed)
    coarse = rng.integers(0, 256, size=(n, 16, 16, channels), dtype=np.uint8)
    contrast = rng.uniform(0.25, 1.0, size=n)
    offset = rng.uniform(0.0, 1.0, size=n) * 255.0 * (1.0 - contrast)
    quality = [cv2.IMWRITE_JPEG_QUALITY, int(wl["jpeg_quality"])]

    def encode(i: int) -> bytes:
        smooth = cv2.resize(coarse[i], (size, size),
                            interpolation=cv2.INTER_CUBIC)
        image = np.clip(smooth * contrast[i] + offset[i], 0, 255)
        ok, data = cv2.imencode(".jpg", image.astype(np.uint8), quality)
        if not ok:
            raise RuntimeError(f"row {i} could not be encoded")
        return data.tobytes()

    with ThreadPoolExecutor(max_workers=8) as pool:
        return list(pool.map(encode, range(n)))


def resize_stage(ctx):
    """The pipeline's first stage: decode (a bytes column) and resize to
    the network's input size, on the host."""
    from mmlspark_tpu.stages.image import ImageTransformer

    col, size = ctx.workload["input_col"], ctx.config["image_size"]
    return ImageTransformer(input_col=col, output_col=col).resize(size, size)


def resize_align_corners(image: np.ndarray, size: int) -> np.ndarray:
    """Bilinear resize to ``size`` square with the corners on the corners
    (output row ``y`` reads source row ``y (h - 1) / (size - 1)``), rounded
    half up: what ``ImageTransformer.resize`` is documented to compute,
    from the definition in float64."""
    h, w = image.shape[:2]
    fy = np.arange(size) * ((h - 1) / (size - 1))
    fx = np.arange(size) * ((w - 1) / (size - 1))
    y0, x0 = np.floor(fy).astype(int), np.floor(fx).astype(int)
    y1, x1 = np.minimum(y0 + 1, h - 1), np.minimum(x0 + 1, w - 1)
    wy, wx = (fy - y0)[:, None, None], (fx - x0)[None, :, None]
    rows0, rows1 = image[y0].astype(np.float64), image[y1].astype(np.float64)
    top = rows0[:, x0] * (1 - wx) + rows0[:, x1] * wx
    bottom = rows1[:, x0] * (1 - wx) + rows1[:, x1] * wx
    return np.floor(top * (1 - wy) + bottom * wy + 0.5).astype(np.uint8)


def plain_pixels(ctx, encoded: list) -> np.ndarray:
    """``[N, S, S, C]`` uint8: the rows decoded by OpenCV (blue first, the
    order the program's image columns hold) and resized to the network's
    input size, with no code of the program."""
    import cv2

    size = ctx.config["image_size"]
    return np.stack([resize_align_corners(
        cv2.imdecode(np.frombuffer(data, np.uint8), cv2.IMREAD_COLOR), size)
        for data in encoded])


def bytes_table(ctx, encoded: list):
    from mmlspark_tpu.data.table import DataTable

    column = np.empty(len(encoded), dtype=object)
    column[:] = encoded
    return DataTable({ctx.workload["input_col"]: column})


class Scorer(table_score.Scorer):
    """The fitted pipeline with its table of encoded images, and the
    sampled answers (``table_score.Scorer.score`` keeps them)."""

    def __init__(self, ctx):
        from mmlspark_tpu.core.pipeline import PipelineModel
        from mmlspark_tpu.models.image_featurizer import ImageFeaturizer
        from mmlspark_tpu.stages.image import UnrollImage

        wl = ctx.workload
        self.wl = wl
        self.data = make_rows(ctx)
        self.table = bytes_table(ctx, self.data)
        self.model = PipelineModel([
            resize_stage(ctx),
            UnrollImage(input_col=wl["input_col"],
                        output_col=wl["unrolled_col"]),
            ImageFeaturizer(model=table_score.make_bundle(ctx),
                            input_col=wl["input_col"],
                            output_col=wl["output_col"],
                            cut_output_layers=1,
                            minibatch_size=wl["minibatch_size"])])
        self.rng = np.random.default_rng([ctx.seed, 1])
        self.sampled_rows: list = []
        self.sampled_scores: list = []
        self.missing = 0


def setup(ctx) -> dict:
    wl = ctx.workload
    t0 = time.perf_counter()
    scorer = Scorer(ctx)
    t1 = time.perf_counter()
    # the padded tail: a short call that is no multiple of the minibatch;
    # this call compiles the one shape (or loads it from the cache)
    short = min(wl["minibatch_size"] + wl["warmup_tail_rows"], wl["rows"])
    scorer.score(bytes_table(ctx, scorer.data[:short]), short,
                 always=(0, wl["minibatch_size"] - 1, wl["minibatch_size"],
                         short - 1))
    t2 = time.perf_counter()
    scorer.score(scorer.table, wl["rows"], always=(0, wl["rows"] - 1))
    ctx.say(f"pipeline_score set-up: weights and "
            f"{sum(map(len, scorer.data)) / 1e6:.1f} MB of encoded images "
            f"{t1 - t0:.2f} s, short call {t2 - t1:.2f} s, whole pass "
            f"{time.perf_counter() - t2:.2f} s")
    return {"scorer": scorer}


def reference_readings(ctx, state: dict, quant: str | None = None,
                       fault: str | None = None) -> np.ndarray:
    """Reference pooled features, one row per sampled answer, of the
    sampled rows' :func:`plain_pixels`; ``quant`` computes them in a lower
    precision (the control), ``fault`` plants a fault in them (both stand
    in the program's place, for ``calibrate.py`` and the tests)."""
    import jax
    import jax.numpy as jnp

    scorer, cfg, ref = state["scorer"], ctx.config, ctx.reference()
    unique = sorted(set(scorer.sampled_rows))
    pixels = plain_pixels(ctx, [scorer.data[i] for i in unique])
    if fault == "channels_swapped":
        pixels = pixels[..., ::-1]

    def make(key):
        params = ref.make_params(cfg, key)
        width = params["head/kernel"].shape[0]
        # an identity in the head's place hands the pooled features through
        return dict(params, **{"head/kernel": jnp.eye(width),
                               "head/bias": jnp.zeros((width,))})

    params = jax.jit(make)(ctx.key())
    forward = jax.jit(lambda p, x: ref.forward(p, x, cfg, quant))
    block = ctx.workload["reference_block_rows"]
    out = []
    for start in range(0, len(pixels), block):
        part = pixels[start:start + block]
        pad = block - len(part)
        if pad:
            part = np.concatenate([part, np.zeros((pad,) + part.shape[1:],
                                                  part.dtype)])
        out.append(np.asarray(forward(params, part))[:block - pad])
    features = np.concatenate(out)
    at = {row: i for i, row in enumerate(unique)}
    features = features[[at[row] for row in scorer.sampled_rows]]
    return np.roll(features, 1, axis=0) if fault == "rows_shifted" \
        else features


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
