"""Device-plan audit structures — the symbolic replay of the pipeline
planner's segmentation.

The audit answers, before any data moves: which stage runs will fuse into
one compiled program, where fusion breaks (and why), and how many
H2D uploads / D2H fetch rounds a transform over N rows will cost against
the one-per-minibatch contract. It reuses the planner's own segmentation
(``core/plan.collect_segment``) with the abstract
:meth:`~mmlspark_tpu.analysis.info.TableSchema.entry_meta` probe standing
in for the concrete table, so the predicted plan is the executed plan by
construction. Crossing arithmetic goes through
``core/plan.predict_segment_minibatches`` (the executor's dp-rounded
minibatch sizing) — nothing here compiles, uploads, or fetches.

The audit's **multi-chip mode** lives in
:mod:`mmlspark_tpu.analysis.spmd` (:func:`spmd_audit` below delegates):
the same symbolic segment replay, additionally verifying each fused
segment's SPMD behavior — entry batch sharded over the data axes,
minibatch walk divisible by the dp extent, and zero manual collectives
in the composite (inference relies on XLA-inserted resharding only).
See docs/spmd_analysis.md.
"""

from __future__ import annotations

import dataclasses
from typing import Any


@dataclasses.dataclass
class PlanSegmentReport:
    """One executor step: a fused device run or a single host stage.

    ``out_dtypes`` carries the step's predicted per-column output dtypes
    — for device segments the eval_shape-traced truth (``ArrayMeta``
    dtypes the composite restores on emit, whatever the precision
    policy computes in); for host steps the schema-predicted dtype of
    each declared output. ``precision`` names the segment's resolved
    serving precision (``"f32"`` when no policy applies) and
    ``tolerance`` its expected max-abs parity bound vs the f32 offline
    transform (docs/quantization.md)."""

    kind: str                      # "device" | "host"
    start: int                     # first stage index (inclusive)
    end: int                       # last stage index (exclusive)
    stages: list                   # stage type names
    entry_col: str | None = None   # fused runs: the one uploaded column
    minibatches: int | None = None  # crossing rounds (None = not predictable)
    notes: list = dataclasses.field(default_factory=list)
    out_dtypes: dict = dataclasses.field(default_factory=dict)
    precision: str | None = None   # device segments: resolved policy mode
    tolerance: float | None = None  # expected parity bound for it

    def describe(self) -> str:
        names = "→".join(self.stages)
        head = f"[{self.start}:{self.end}] {self.kind}: {names}"
        if self.kind == "device":
            head += f" (entry {self.entry_col!r}"
            if self.minibatches is not None:
                head += f", {self.minibatches} minibatch round(s)"
            if self.precision is not None:
                head += f", precision {self.precision}"
                if self.tolerance is not None:
                    head += f" (expected parity ≤ {self.tolerance:g})"
            head += ")"
        elif self.minibatches:
            head += f" ({self.minibatches} minibatch round(s) on its own path)"
        if self.out_dtypes:
            cols = ", ".join(f"{c}:{d}" for c, d in self.out_dtypes.items())
            head += f" → {cols}"
        return head


@dataclasses.dataclass
class PlanAudit:
    """The predicted execution plan of one transform call.

    ``uploads``/``fetches`` are the predicted H2D / D2H crossing totals per
    transform over the audited row count — ``None`` when device work exists
    but the row count (or a stage's row effect) is unknown. A pipeline with
    no device work predicts 0 exactly, whatever the row count.
    """

    segments: list[PlanSegmentReport] = dataclasses.field(
        default_factory=list)
    uploads: int | None = 0
    fetches: int | None = 0

    @property
    def device_segments(self) -> list[PlanSegmentReport]:
        return [s for s in self.segments if s.kind == "device"]

    def structure(self) -> list[tuple[str, int]]:
        """``[(kind, n_stages), ...]`` — comparable to
        ``core/plan.describe_plan`` output shapes."""
        return [(s.kind, s.end - s.start) for s in self.segments]

    def format(self) -> str:
        lines = [s.describe() for s in self.segments]
        if self.uploads is None:
            lines.append("crossings: not statically predictable "
                         "(unknown row count or row-changing stage)")
        else:
            lines.append(f"crossings: {self.uploads} H2D upload(s), "
                         f"{self.fetches} D2H fetch round(s) predicted")
        return "\n".join(lines)


@dataclasses.dataclass
class TrainPreprocessAudit:
    """Pre-flight replay of a train-input ``DevicePreprocess`` spec —
    the train segment's face of the plan audit.

    ``infer_schema`` for the preprocess spec: the symbolic geometry walk
    (``DevicePreprocess.out_shape``) validates the spec against the
    source image geometry (out-of-bounds source crop, reflect padding
    wider than the image, channel-count mismatches on mean/std) BEFORE
    any batch is assembled, and the byte predictions price both wire
    forms of the thin-wire A/B per batch:

    * ``thin_bytes`` — source-resolution uint8 on the wire (geometry +
      normalize replayed in the jitted step);
    * ``host_bytes`` — the host-preprocess baseline: float32 at the
      POST-geometry width.

    The predictions are exact — ``tests/test_train_preprocess.py`` holds
    ``thin_bytes`` equal to the bytes the obs registry observes at the
    ``core/plan.train_commit`` seam per committed batch.
    """

    in_shape: tuple               # (h, w, c) source geometry
    out_shape: tuple              # (h, w, c) after geometry replay
    batch_size: int
    thin_bytes: int               # per-batch uint8 wire (x payload only)
    host_bytes: int               # per-batch f32 host-preprocess wire
    reduction: float              # host_bytes / thin_bytes

    def describe(self) -> str:
        return (f"train preprocess: {self.in_shape} uint8 → "
                f"{self.out_shape} f32 on device; wire "
                f"{self.thin_bytes} B/batch thin vs {self.host_bytes} B "
                f"host-preprocessed ({self.reduction:.2f}x reduction)")


def audit_train_preprocess(spec: Any, input_shape: tuple,
                           batch_size: int) -> TrainPreprocessAudit:
    """Statically validate a ``DevicePreprocess`` spec over a source
    image geometry and predict the per-batch H2D byte cost of both wire
    forms. Raises :class:`~mmlspark_tpu.analysis.info.SchemaError` on a
    geometry the device chain would reject at trace time."""
    import numpy as np

    from mmlspark_tpu.analysis.info import SchemaError
    from mmlspark_tpu.train.preprocess import DevicePreprocess

    spec = DevicePreprocess.parse(spec)
    if spec is None:
        raise SchemaError("preprocess-missing",
                          "audit_train_preprocess needs a spec; got None")
    try:
        out = spec.out_shape(tuple(input_shape))
    except ValueError as e:
        raise SchemaError("preprocess-geometry", str(e)) from e
    bs = int(batch_size)
    thin = bs * int(np.prod(input_shape))
    host = bs * int(np.prod(out)) * 4
    return TrainPreprocessAudit(
        in_shape=tuple(int(d) for d in input_shape),
        out_shape=tuple(out), batch_size=bs, thin_bytes=thin,
        host_bytes=host, reduction=round(host / thin, 4))


def spmd_audit(stages: list, meta_of: Any, n_rows: int | None = None):
    """The plan audit's multi-chip mode: delegate to
    :func:`mmlspark_tpu.analysis.spmd.audit_plan_spmd` (lazy import —
    the SPMD verifier pulls in jaxpr machinery this module's pure
    report types must not depend on)."""
    from mmlspark_tpu.analysis.spmd import audit_plan_spmd
    return audit_plan_spmd(stages, meta_of, n_rows=n_rows)


def standalone_crossings(stage: Any, schema: Any, n_rows: int | None
                         ) -> int | None:
    """Crossing rounds a stage costs when it runs OUTSIDE a fused segment
    (the host walk). Most host stages cost zero; a lone ``JaxModel`` runs
    as a segment of one through the planner, and an ``ImageFeaturizer``
    executes its internal resize→forward plan. Returns None when the stage
    does device work but the count is not predictable."""
    from mmlspark_tpu.models.image_featurizer import ImageFeaturizer
    from mmlspark_tpu.models.jax_model import JaxModel

    if isinstance(stage, ImageFeaturizer):
        if stage.model is None:
            return 0
        from mmlspark_tpu.analysis.analyzer import analyze
        report = analyze(stage._stages(), schema, n_rows=n_rows)
        return report.plan.uploads if report.plan is not None else None
    if isinstance(stage, JaxModel):
        if stage.model is None or n_rows == 0:
            return 0
        if n_rows is None:
            return None
        from mmlspark_tpu.core import plan
        from mmlspark_tpu.core.stage import ArrayMeta

        # the segment of one its transform runs: the block as coerced has
        # the model's own spec, and stays uint8 only where the column is
        # (its bytes decide whether a minibatch crosses in pieces)
        info = schema.get(stage.input_col)
        spec = ArrayMeta(tuple(stage.model.input_spec),
                         "uint8" if info is not None
                         and info.dtype == "uint8" else "float32")
        seg = plan.collect_segment([stage], 0, lambda _col: spec,
                                   min_stages=1)
        return plan.predict_segment_minibatches(seg, n_rows)
    return 0
