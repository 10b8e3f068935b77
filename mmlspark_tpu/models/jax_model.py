"""JaxModel — batched DNN inference as a pipeline stage.

The CNTKModel analog (reference: cntk-model/src/main/scala/CNTKModel.scala).
The reference broadcasts serialized model bytes to Spark executors, clones
the graph per task, marshals rows element-by-element into JNI FloatVectors,
evaluates minibatches, and merges outputs back row-wise
(CNTKModel.scala:51-114). The TPU-native redesign:

* the model is a :class:`ModelBundle` (flax module + pytree) — no broadcast
  or per-task clone needed. ``transform`` owns the validation and coercion
  of the input column and nothing else: the block runs as a segment of one
  stage through the planner's executor
  (:func:`mmlspark_tpu.core.plan.run_entered_segment`), which compiles,
  places, caches and feeds a lone model's forward as it does a fused run's,
* input coercion is at most one vectorized host copy (``column_matrix`` /
  image stacking) instead of per-element JNI sets, and none where the
  column's rows already lie in one matrix (``column_matrix`` hands back a
  read-only view of it),
* what the executor (``core/plan.py``) does for every segment, it does
  here: fixed-shape minibatches with a padded tail (one XLA program per
  shape), asynchronous dispatch (host marshalling of batch *i+1* overlaps
  device compute of batch *i*), and **data parallelism over the device
  mesh**: params live device-resident (transferred once, replicated) and
  each minibatch is committed batch-sharded over the ``dp``/``fsdp`` axes
  — the reference's Spark-partition DP inference (CNTKModel.scala:248-256)
  mapped to one host feeding a mesh; outputs come back through a bounded
  window of async fetches (no per-minibatch sync),
* output-node selection by name or index matches CNTK node selection
  (CNTKModel.scala:98-108).
"""

from __future__ import annotations

from typing import Any, Iterator

import numpy as np

from mmlspark_tpu.core.params import Param
# minibatches lives in core.plan (shared with fused pipeline segments);
# re-exported here for the bridge and existing callers
from mmlspark_tpu.core.plan import (  # noqa: F401
    collect_segment, dp_rounded_minibatch, mesh_dp, minibatches,
    pipeline_minibatches, run_entered_segment,
)
from mmlspark_tpu.core.schema import is_image_column
from mmlspark_tpu.core.stage import (
    ArrayMeta, DeviceOp, DeviceStage, HasInputCol, HasOutputCol, TracedMeta,
    Transformer,
)
from mmlspark_tpu.data.table import DataTable
from mmlspark_tpu.models.bundle import ModelBundle, PREPROCESSORS


def _source_dtype(col: np.ndarray, sample: Any) -> Any:
    """uint8 sources stay uint8 (¼ the host→device bytes; the on-device
    forward upcasts) — decoded image bytes are the hot inference input, as
    in the reference's byte-typed image schema. Everything else → float32."""
    d = getattr(np.asarray(sample), "dtype", None)
    return np.uint8 if d == np.uint8 else np.float32


def coerce_input_matrix(table: DataTable, column: str,
                        input_spec: tuple) -> np.ndarray:
    """Coerce an input column to a [N, *input_spec] array (uint8 or float32).

    Accepts: image-struct columns (stacked HWC), vector columns (reshaped to
    the model spec), scalar numeric columns. The dtype-coercion analog of
    CNTKModel.scala:228-245, vectorized. A vector column that is already
    one matrix comes back as a read-only view of it
    (:meth:`DataTable.column_matrix`): upload from it, do not write to it.
    """
    col = table[column]
    if is_image_column(table, column):
        # uint8 only when EVERY row is uint8 — a lone float row must not be
        # silently truncated into a uint8 buffer
        datas = [np.asarray(r["data"]) for r in col]
        dtype = (np.uint8 if all(d.dtype == np.uint8 for d in datas)
                 else np.float32)
        first = datas[0]
        if all(d.shape == first.shape and d.dtype == dtype for d in datas):
            # uniform shape+dtype: ONE C-level bulk copy
            batch = np.stack(datas)
        else:
            # mixed-dtype/shape fallback: preallocated per-row assignment
            # (each row cast into the target buffer, no intermediate stack)
            batch = np.empty((len(datas),) + first.shape, dtype=dtype)
            for i, d in enumerate(datas):
                batch[i] = d
    elif col.dtype == object:
        batch = table.column_matrix(column,
                                    dtype=_source_dtype(col, col[0]))
    else:
        batch = table.column_matrix(column, dtype=np.float32)
    want = (len(table),) + tuple(input_spec)
    if batch.shape != want:
        if int(np.prod(batch.shape)) != int(np.prod(want)):
            raise ValueError(
                f"column {column!r} has shape {batch.shape[1:]} per row; "
                f"model expects {tuple(input_spec)}")
        batch = batch.reshape(want)
    return batch


class JaxModel(Transformer, DeviceStage, HasInputCol, HasOutputCol):
    """Applies a jit-compiled model to an input column, in minibatches."""

    model = Param(default=None, doc="ModelBundle to apply", is_complex=True)
    minibatch_size = Param(
        default=None, doc="device minibatch size (None = config default)",
        type_=int)
    output_node = Param(
        default=None, doc="output node to select, by name",
        type_=str)
    output_node_index = Param(
        default=None, doc="output node to select, by index", type_=int)
    mesh_spec = Param(
        default=None, is_complex=True,
        doc="inference mesh layout (MeshSpec/dict); None = data parallelism "
            "over every local device; an explicit spec smaller than the "
            "host's device count uses a prefix of the local devices")
    max_inflight = Param(
        default=8, type_=int, validator=Param.gt(1),
        doc="max minibatch outputs resident on device at once during "
            "transform(); older outputs are fetched to host as newer "
            "batches dispatch, bounding HBM use on very large tables "
            "while keeping the async upload/compute/fetch overlap. "
            "Minimum 2: a window of 1 would serialize fetch with compute")

    def __getstate__(self):
        # the planner's compiled-segment cache (jitted closures, device
        # arrays, a lock) doesn't pickle; drop on serialize
        d = self.__dict__.copy()
        d.pop("_plan_cache", None)
        d.pop("_plan_lock", None)
        return d

    def set_model_location(self, path: str) -> "JaxModel":
        """Load the model from a published bundle file — the
        ``CNTKModel.setModelLocation`` analog (reference:
        CNTKModel.scala:151-154); pair with ``ModelDownloader`` for the
        zoo-download path."""
        from mmlspark_tpu.data.downloader import load_bundle_file
        self.set(model=load_bundle_file(path))
        return self

    def _resolve_node(self, bundle: ModelBundle) -> str:
        if self.output_node is not None:
            return bundle.resolve_output(self.output_node)
        if self.output_node_index is not None:
            return bundle.resolve_output(self.output_node_index)
        return bundle.resolve_output(None)

    def transform(self, table: DataTable) -> DataTable:
        bundle: ModelBundle = self.model
        if bundle is None:
            raise ValueError("JaxModel: no model set")
        self._resolve_node(bundle)  # an unknown node raises before any work
        if len(table) == 0:
            return table.with_column(self.output_col, [])

        def enter() -> tuple:
            batch = coerce_input_matrix(table, self.input_col,
                                        bundle.input_spec)
            # the segment is built on the block as coerced, so device_fn's
            # reshape to input_spec is the identity
            meta = ArrayMeta(batch.shape[1:], str(batch.dtype))
            return collect_segment([self], 0, lambda _col: meta,
                                   min_stages=1), batch, {}

        return run_entered_segment(table, enter, self, pipeline_minibatches)

    # ---- static schema inference ----

    def infer_schema(self, schema: Any) -> Any:
        """The traced truth: the predicted output layout comes from
        ``jax.eval_shape`` over the same forward ``device_fn`` composes —
        no data, no device execution, no compilation. A provable per-row
        size mismatch against the bundle's ``input_spec`` is rejected here
        instead of as an XLA shape error after the H2D upload."""
        from mmlspark_tpu.analysis.info import (
            KIND_IMAGE, ColumnInfo, SchemaError,
        )
        out = schema.copy()
        info = out.get(self.input_col)
        if info is None:
            if schema.exact:
                raise SchemaError(
                    "missing-input-column",
                    f"JaxModel reads missing column {self.input_col!r}; "
                    f"available: {list(schema)}")
            info = ColumnInfo.unknown()
        bundle: ModelBundle = self.model
        if bundle is None:
            raise SchemaError(
                "model-not-set",
                "JaxModel has no model bundle; set model= or "
                "set_model_location() before running the pipeline")
        try:
            node = self._resolve_node(bundle)
        except Exception as e:
            raise SchemaError("bad-output-node", str(e))
        spec = tuple(bundle.input_spec)
        want = int(np.prod(spec))
        size = info.row_size
        if size is not None and size != want:
            kind_note = ("an image column unrolling to"
                         if info.kind == KIND_IMAGE else "per-row size")
            raise SchemaError(
                "input-size-mismatch",
                f"column {self.input_col!r} is {kind_note} {size} values "
                f"but model {bundle.name!r} expects input_spec {spec} "
                f"({want} values)")
        meta = schema.entry_meta(self.input_col)
        if meta is None or int(np.prod(meta.shape)) != want:
            # layout not statically coercible; trace with the model's own
            # spec (what coerce_input_matrix reshapes to)
            meta = ArrayMeta(spec, "float32")
        from mmlspark_tpu.core.plan import _stage_device_fn
        op = _stage_device_fn(self, meta)  # memoized eval_shape trace
        if op is None:  # pragma: no cover - defensive; sizes matched above
            raise SchemaError(
                "device-fn-declined",
                f"JaxModel.device_fn declined layout {meta}")
        shape = tuple(op.out_meta.shape)
        if shape == ():
            out.columns[self.output_col] = ColumnInfo.scalar(
                op.out_meta.dtype)
        else:
            out.columns[self.output_col] = ColumnInfo.vector(
                int(np.prod(shape)), op.out_meta.dtype)
        return out

    # ---- DeviceStage protocol: lets the pipeline planner fuse this model
    #      with adjacent device stages into one compiled program ----

    def device_program_token(self) -> Any:
        bundle = self.model
        return (None if bundle is None else
                (id(bundle.module), bundle.preprocess),
                self.input_col, self.output_col,
                self.output_node, self.output_node_index,
                self.minibatch_size, repr(self.mesh_spec))

    def device_cache_token(self) -> Any:
        return (self.device_program_token(),
                id(getattr(self.model, "params", None)))

    def device_fingerprint(self) -> Any:
        """Stable content identity for the persistent AOT compile cache
        (core/compile_cache.py): the bundle's weights digest replaces
        the ``id()``s of :meth:`device_cache_token`, so two
        processes loading the same artifact key the same programs."""
        bundle = self.model
        if bundle is None:
            return None
        from mmlspark_tpu.core.compile_cache import bundle_digest
        return ("JaxModel", bundle_digest(bundle),
                *self.device_program_token()[1:])

    def device_fn(self, meta: ArrayMeta) -> DeviceOp | None:
        """The model's forward (uint8 ships thin and upcasts on device,
        then the bundle's preprocess and the selected output node) as a
        composable op, the one every path compiles. Declines on a per-row
        size mismatch so the host path raises its canonical shape error."""
        bundle: ModelBundle = self.model
        if bundle is None:
            return None
        spec = tuple(bundle.input_spec)
        if int(np.prod(meta.shape)) != int(np.prod(spec)):
            return None
        node = self._resolve_node(bundle)
        pre = (PREPROCESSORS.get(bundle.preprocess)
               if bundle.preprocess else None)

        def fwd(params, x):
            import jax.numpy as jnp
            x = x.reshape((x.shape[0],) + spec)
            if x.dtype == jnp.uint8:  # uint8 ships thin, computes as f32
                x = x.astype(jnp.float32)
            if pre is not None:
                x = pre(x)
            return bundle.module.apply({"params": params}, x, output=node)

        def traced() -> ArrayMeta:
            import jax
            out = jax.eval_shape(
                fwd, bundle.params,
                jax.ShapeDtypeStruct((1,) + tuple(meta.shape),
                                     np.dtype(meta.dtype)))
            return ArrayMeta(tuple(out.shape[1:]), str(out.dtype))

        # the output layout costs a whole trace of the forward: left to
        # whoever reads it, which a run that ends in this stage never does
        return DeviceOp(fwd, TracedMeta(traced), params=bundle.params)

    def transform_stream(self, tables: Any) -> Iterator[DataTable]:
        """Score a stream of DataTable chunks with bounded memory.

        The compiled program and device-resident params are shared across
        chunks (this stage's compiled-segment cache): no recompiles or
        re-uploads — pair with ``data.readers.stream_images`` for
        ImageNet-shard-scale scoring without materializing the dataset.
        """
        for chunk in tables:
            yield self.transform(chunk)
