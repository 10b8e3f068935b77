"""Pipeline-stage contracts: Transformer / Estimator plus column-role mixins.

Analog of SparkML's ``Transformer``/``Estimator`` as used throughout the
reference, with the reference's shared column-role mixins
``HasInputCol/HasOutputCol/HasLabelCol/...`` (reference:
core/contracts/src/main/scala/Params.scala:112-176). Stages are registered
on subclass creation, which powers the fuzz suite and doc generation the way
jar-reflection powers the reference's ``Fuzzing.scala`` and codegen
(reference: core/utils/src/main/scala/JarLoadingUtils.scala:17-80).
"""

from __future__ import annotations

import dataclasses
import itertools
from typing import Any, Callable

from mmlspark_tpu.core.params import Param, Params
from mmlspark_tpu.core import serialize as _ser
from mmlspark_tpu.data.table import DataTable


_UID_COUNTER = itertools.count()

# global registry: class path → class; drives fuzzing + docgen
STAGE_REGISTRY: dict[str, type] = {}


class PipelineStage(Params):
    """Base of every stage. Named, parameterized, persistable."""

    def __init__(self, **kwargs: Any):
        self._post_init()
        super().__init__(**kwargs)

    def _post_init(self) -> None:
        # split from __init__ so deserialization can bypass param validation
        if not hasattr(self, "_uid") or self._uid is None:
            self._uid = f"{type(self).__name__}_{next(_UID_COUNTER)}"

    def __init_subclass__(cls, **kwargs: Any):
        super().__init_subclass__(**kwargs)
        if not cls.__name__.startswith("_"):
            STAGE_REGISTRY[_ser.class_path(cls)] = cls

    @property
    def uid(self) -> str:
        return self._uid

    # -- persistence contract (every stage is writable/readable,
    #    analog of MLWritable via ComplexParamsWritable) --

    def save(self, path: str, overwrite: bool = True) -> None:
        _ser.save_stage(self, path, overwrite=overwrite)

    @staticmethod
    def load(path: str) -> "PipelineStage":
        return _ser.load_stage(path)

    def _save_extra(self, directory: str) -> None:
        """Hook for state outside the param store (rare)."""

    def _load_extra(self, directory: str) -> None:
        pass

    # -- static schema inference (the transformSchema analog) --
    #
    # The pre-flight analyzer (mmlspark_tpu/analysis) walks a pipeline's
    # stages calling infer_schema with NO data and NO device execution.
    # A stage maps the incoming abstract TableSchema to the schema its
    # transform would produce, raising analysis.info.SchemaError on a
    # contract violation (missing column, wrong kind, size mismatch).
    # The default below is derived from the declared column-role params;
    # stages whose output layout is computable (image geometry, model
    # forwards via jax.eval_shape) override it.

    def _declared_input_columns(self) -> list[str]:
        """Column names this stage reads, per its column-role params."""
        declared = type(self).params()
        cols: list[str] = []
        if "input_col" in declared and self.get("input_col"):
            cols.append(self.get("input_col"))
        if "input_cols" in declared and self.get("input_cols"):
            cols.extend(self.get("input_cols"))
        if isinstance(self, Estimator) and "label_col" in declared \
                and self.get("label_col"):
            cols.append(self.get("label_col"))
        return cols

    def _declared_output_columns(self) -> list[str]:
        declared = type(self).params()
        cols: list[str] = []
        if "output_col" in declared and self.get("output_col"):
            cols.append(self.get("output_col"))
        if "output_cols" in declared and self.get("output_cols"):
            cols.extend(self.get("output_cols"))
        return cols

    def infer_schema(self, schema: Any) -> Any:
        """Map an abstract input schema to this stage's output schema.

        Default: require every declared input column to exist and add the
        declared output columns with unknown layout. Override to compute
        real output dtypes/shapes (and to enforce stronger contracts).
        """
        from mmlspark_tpu.analysis.info import ColumnInfo, SchemaError
        missing = [c for c in self._declared_input_columns()
                   if c not in schema]
        out = schema.copy()
        if missing:
            msg = (f"{type(self).__name__} reads missing column(s) "
                   f"{missing}; available: {list(schema)}")
            if schema.exact:
                raise SchemaError("missing-input-column", msg)
            out.warn("missing-input-column", msg + " (schema is inexact: "
                     "an opaque stage may have added them)", "info")
        for c in self._declared_output_columns():
            out.columns[c] = ColumnInfo.unknown()
        return out

    def infer_rows(self, n: int | None, schema: Any) -> int | None:
        """Predicted output row count for ``n`` input rows (None =
        unknown). Default: row-preserving; sampling/augmenting/dropping
        stages override."""
        return n

    def _infer_state(self, schema: Any, n: int | None
                     ) -> tuple[Any, int | None]:
        """One-pass (schema, rows) inference — the analyzer's entry point.
        Default composes the two public hooks (rows first: ``infer_rows``
        reads the PRE-stage schema); Pipeline/PipelineModel override to
        fold their stages once, so nested analysis work (UDF probes,
        eval_shape traces) runs a single time per walk."""
        rows = None if n is None else self.infer_rows(n, schema)
        return self.infer_schema(schema), rows

    def __repr__(self) -> str:
        sets = ", ".join(f"{k}={v!r}" for k, v in
                         self._simple_param_values().items())
        return f"{type(self).__name__}({sets})"


class Transformer(PipelineStage):
    """A stage mapping DataTable → DataTable."""

    def transform(self, table: DataTable) -> DataTable:
        raise NotImplementedError

    def __call__(self, table: DataTable) -> DataTable:
        return self.transform(table)


class Estimator(PipelineStage):
    """A stage that fits on a DataTable and yields a Transformer (model)."""

    def fit(self, table: DataTable) -> Transformer:
        raise NotImplementedError

    def fit_transform(self, table: DataTable) -> DataTable:
        return self.fit(table).transform(table)


# ---- column-role mixins (Params.scala:112-176 analog) ----

class HasInputCol:
    input_col = Param(default="input", doc="name of the input column",
                      type_=str)


class HasOutputCol:
    output_col = Param(default="output", doc="name of the output column",
                       type_=str)


class HasInputCols:
    input_cols = Param(default=None, doc="names of the input columns",
                       type_=(list, tuple))


class HasOutputCols:
    output_cols = Param(default=None, doc="names of the output columns",
                        type_=(list, tuple))


class HasLabelCol:
    label_col = Param(default="label", doc="name of the label column",
                      type_=str)


class HasFeaturesCol:
    features_col = Param(default="features", doc="name of the features column",
                         type_=str)


class UnaryTransformer(Transformer, HasInputCol, HasOutputCol):
    """A transformer producing one output column from one input column."""

    def _transform_column(self, values: Any, table: DataTable) -> Any:
        raise NotImplementedError

    def transform(self, table: DataTable) -> DataTable:
        out = self._transform_column(table[self.input_col], table)
        return table.with_column(self.output_col, out)


# ---- device-resident execution capability (the pipeline-fusion protocol) --

@dataclasses.dataclass(frozen=True)
class ArrayMeta:
    """Shape/dtype contract for one column batched as a device array.

    ``shape`` is the per-row shape (the batch axis is implicit), ``dtype``
    a numpy dtype string, and ``is_image`` marks stacked HWC image structs
    (whose host form is a column of image dicts). This is what a
    :class:`DeviceStage` sees when asked whether it can run on device.
    """

    shape: tuple
    dtype: str
    is_image: bool = False


class TracedMeta:
    """An :class:`ArrayMeta` that costs a trace of the model to learn,
    resolved on its first read (a later stage of the run, an emitter that
    uses it): a run that ends in the model, as a lone ``JaxModel.transform``
    does, never pays the trace. Compares and hashes as what it resolves to."""

    __slots__ = ("_resolve", "_meta")

    def __init__(self, resolve: Callable[[], ArrayMeta]):
        self._resolve = resolve
        self._meta: ArrayMeta | None = None

    def resolved(self) -> ArrayMeta:
        if self._meta is None:
            self._meta = self._resolve()
        return self._meta

    shape = property(lambda self: self.resolved().shape)
    dtype = property(lambda self: self.resolved().dtype)
    is_image = property(lambda self: self.resolved().is_image)

    def __eq__(self, other: Any) -> bool:
        return self.resolved() == (other.resolved() if isinstance(
            other, TracedMeta) else other)

    def __hash__(self) -> int:
        return hash(self.resolved())

    def __repr__(self) -> str:
        return repr(self.resolved())


@dataclasses.dataclass
class DeviceOp:
    """A stage's columnwise device computation.

    ``fn(params, x)`` must be a *pure* jax function mapping a
    ``[B, *in_meta.shape]`` array to ``[B, *out_meta.shape]`` — the planner
    composes adjacent ops into ONE jitted program, so fn must not perform
    host transfers, I/O, or Python-side mutation. ``params`` is a pytree of
    host arrays uploaded once per compiled segment and kept device-resident
    (the broadcast-once analog); stateless ops use the default ``()``.
    """

    fn: Callable
    out_meta: ArrayMeta | TracedMeta
    params: Any = ()


class DeviceStage:
    """Capability mixin: a stage that can describe its computation as a pure
    columnwise array→array jax function, letting the pipeline planner
    (:mod:`mmlspark_tpu.core.plan`) keep data device-resident across stage
    boundaries instead of paying a host round-trip per stage.

    Opting in is best-effort: ``device_fn`` returning ``None`` (for an
    unsupported op list, dtype, or shape) falls back to the stage's host
    ``transform`` with identical semantics. Implementations must keep the
    device math equivalent to the host path — the parity suite
    (tests/test_plan.py) holds fused output to the documented tolerance.

    Two OPTIONAL hooks extend the protocol for sharded serving
    (docs/serving.md): ``device_fn_mesh(meta, mesh)`` — a mesh-aware
    variant the planner prefers at compile/verify time when the segment's
    concrete mesh is resolved (pipeline-parallel stages whose collectives
    bind mesh axes need it; shape inference still uses the plain
    ``device_fn``) — and ``device_param_rules(path, leaf)`` — per-leaf
    ``PartitionSpec`` placement consulted by
    :func:`mmlspark_tpu.parallel.mesh.param_shardings` when the segment
    compiles on a model-parallel mesh.
    """

    def device_input_col(self) -> str | None:
        """The single column the device computation consumes (None = this
        stage cannot run on device for the current configuration)."""
        return getattr(self, "input_col", None)

    def device_output_col(self) -> str | None:
        """The column the device computation produces."""
        return getattr(self, "output_col", None)

    def device_cache_token(self) -> Any:
        """A cheap fingerprint of the configuration the device computation
        depends on; a changed token invalidates the planner's compiled
        segment. The default covers stages fully described by their simple
        params; stages with complex params (models, fitted plans) must
        override to include their identity."""
        vals = self._simple_param_values() if hasattr(
            self, "_simple_param_values") else {}
        return tuple(sorted((k, repr(v)) for k, v in vals.items()))

    def device_program_token(self) -> Any:
        """:meth:`device_cache_token` less the identity of the parameter
        *objects* ``device_fn`` hands over. When only the rest of the
        cache token moved, the planner keeps the compiled program and
        uploads the new parameters onto the old ones' shardings. The
        default suits a stage whose parameters are never reassigned."""
        return self.device_cache_token()

    def device_fingerprint(self) -> Any:
        """A STABLE content identity for the persistent AOT compile
        cache (core/compile_cache.py), or ``None`` to opt the segment
        out of cross-process caching. Unlike ``device_cache_token`` —
        which may (and for model stages does) lean on ``id()`` because
        it only guards the in-process compiled-segment cache — a
        fingerprint must hash *content*: two processes loading the same
        artifact must produce the same fingerprint, and any change that
        could alter the compiled program must change it. The default
        covers stages fully described by their simple params; stages
        with complex params (models) must override with a weights
        digest or return ``None``."""
        if hasattr(self, "_complex_param_values") and \
                any(v is not None
                    for v in self._complex_param_values().values()):
            return None  # complex params: content unknown by default
        vals = self._simple_param_values() if hasattr(
            self, "_simple_param_values") else {}
        return (f"{type(self).__module__}.{type(self).__qualname__}",
                tuple(sorted((k, repr(v)) for k, v in vals.items())))

    def device_fn(self, meta: ArrayMeta) -> DeviceOp | None:
        """Describe this stage's computation on a column of ``meta`` layout,
        or ``None`` to decline (host fallback)."""
        return None

    def device_emit(self, table: DataTable, values: Any,
                    meta: ArrayMeta, ctx: dict) -> DataTable:
        """Write the fused computation's host-fetched output (``values``,
        shaped ``[N, *meta.shape]``) into the table the way this stage's
        host ``transform`` would. ``ctx`` carries segment-entry context
        (e.g. image paths captured during coercion)."""
        out = values if values.ndim == 1 else list(values)
        return table.with_column(self.device_output_col(), out)


class LambdaTransformer(Transformer):
    """Wraps an arbitrary table→table function as a stage (UDFTransformer
    analog). The function is persisted by pickle."""

    fn = Param(default=None, doc="function DataTable -> DataTable",
               is_complex=True)

    def transform(self, table: DataTable) -> DataTable:
        return self.fn(table)

    def infer_schema(self, schema: Any) -> Any:
        """Probe the UDF on a 0-row table realizing the schema: the column
        *set* it produces is observed concretely, while cell layouts of
        columns it touches become unknown (nothing provable about a UDF's
        values from zero rows). If the UDF cannot run on an empty table the
        schema degrades to inexact instead of failing the analysis."""
        from mmlspark_tpu.analysis.info import ColumnInfo, TableSchema
        try:
            empty = schema.empty_table()
            probed = self.fn(empty)
        except Exception as e:
            out = schema.as_inexact()
            out.warn(
                "opaque-stage",
                f"LambdaTransformer fn could not be probed on an empty "
                f"table ({type(e).__name__}: {e}); downstream column "
                "checks are best-effort", "info")
            return out
        cols = {}
        for name in probed.columns:
            if name in empty and name in schema.columns \
                    and probed[name] is empty[name]:
                cols[name] = schema.columns[name].copy()  # untouched
            else:
                cols[name] = ColumnInfo.unknown(
                    meta=dict(probed.column_meta(name)))
        out = TableSchema(cols, exact=schema.exact)
        out.pending = list(schema.pending)  # findings ride along the fold
        return out

    def infer_rows(self, n: int | None, schema: Any) -> int | None:
        # a UDF may filter or expand rows; assume row-preserving (the
        # common case) — the plan audit's crossing prediction documents
        # this as an approximation for row-changing UDFs
        return n
