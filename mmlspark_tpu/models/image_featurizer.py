"""ImageFeaturizer — transfer-learning featurization from zoo models.

Analog of the reference's ``src/image-featurizer/`` (reference:
ImageFeaturizer.scala:116-140): resize the image to the model's input
dims, normalize, run the truncated network, emit the activation vector.
``cut_output_layers`` counts named output nodes dropped from the end —
0 keeps the head (logits), 1 yields the penultimate features, matching
the reference's ``setCutOutputLayers`` over the zoo schema's
``layerNames``.
"""

from __future__ import annotations

from typing import Any

import numpy as np

from mmlspark_tpu.core.params import Param
from mmlspark_tpu.core.stage import (
    ArrayMeta, DeviceOp, DeviceStage, HasInputCol, HasOutputCol, Transformer,
)
from mmlspark_tpu.data.table import DataTable
from mmlspark_tpu.models.bundle import ModelBundle
from mmlspark_tpu.models.jax_model import JaxModel
from mmlspark_tpu.stages.image import ImageTransformer


class ImageFeaturizer(Transformer, DeviceStage, HasInputCol, HasOutputCol):
    """Transfer learning from zoo models: resize to the model's input size,
    unroll, and run a truncated forward pass (``cut_output_layers`` picks the
    intermediate node per the bundle's ``layer_names``). Reference:
    image-featurizer/src/main/scala/ImageFeaturizer.scala:116-140."""

    input_col = Param(default="image", doc="input image column", type_=str)
    output_col = Param(default="features", doc="output feature column",
                       type_=str)
    model = Param(default=None, doc="ModelBundle to featurize with",
                  is_complex=True)
    cut_output_layers = Param(
        default=1, doc="number of output nodes cut from the end "
        "(0 = keep the full head)", type_=int, validator=Param.ge(0))
    minibatch_size = Param(default=None, doc="device minibatch size",
                           type_=int)

    def set_model_by_name(self, name: str, **kwargs: Any) -> "ImageFeaturizer":
        from mmlspark_tpu.models.zoo import get_model
        self.set(model=get_model(name, **kwargs))
        return self

    def set_model_from_repo(self, name: str, repo: Any = None,
                            cache_dir: str | None = None
                            ) -> "ImageFeaturizer":
        """Fetch a *pretrained* bundle through ``ModelDownloader`` (manifest
        + sha256 cache) — the reference's zoo-download → featurize flow
        (ModelDownloader.scala:224-251 → ImageFeaturizer.scala:70-74)."""
        from mmlspark_tpu.data.downloader import (
            ModelDownloader, load_bundle_file,
        )
        path = ModelDownloader(repo, cache_dir).download_by_name(name)
        self.set(model=load_bundle_file(path))
        return self

    def _resolve_cut_node(self, bundle: ModelBundle) -> str:
        cut = self.cut_output_layers
        names = bundle.output_names
        if cut >= len(names):
            raise ValueError(
                f"cut_output_layers={cut} but model has only "
                f"{len(names)} output nodes {names}")
        return names[len(names) - 1 - cut]

    def _stages(self) -> list:
        """The resize→forward stage pair, built once per configuration so
        the planner's compiled-segment cache (keyed by stage identity)
        stays warm across transform calls."""
        bundle: ModelBundle = self.model
        if bundle is None:
            raise ValueError("ImageFeaturizer: no model set")
        h, w = bundle.input_spec[0], bundle.input_spec[1]
        key = (id(bundle), h, w, self._resolve_cut_node(bundle),
               self.minibatch_size, self.input_col, self.output_col)
        cached = self.__dict__.get("_stage_cache")
        if cached is not None and cached[0] == key:
            return cached[1]
        rt = ImageTransformer(
            input_col=self.input_col, output_col=self.input_col,
        ).resize(h, w)
        jm = JaxModel(
            input_col=self.input_col,
            output_col=self.output_col,
            output_node=self._resolve_cut_node(bundle),
            minibatch_size=self.minibatch_size,
        )
        jm.set(model=bundle)
        self.__dict__["_stage_cache"] = (key, [rt, jm])
        return [rt, jm]

    def __getstate__(self):
        d = self.__dict__.copy()
        for k in ("_stage_cache", "_plan_cache", "_plan_lock"):
            d.pop(k, None)
        return d

    def transform(self, table: DataTable) -> DataTable:
        # resize + truncated forward go through the pipeline planner: on
        # device-friendly tables they fuse into ONE compiled program (single
        # H2D upload of the raw uint8 batch per minibatch — ~h*w/32²× fewer
        # bytes than shipping resized f32 — and one async fetch); anything
        # the planner declines runs the same two stages on host, unchanged
        from mmlspark_tpu.core import plan
        return plan.execute_stages(self._stages(), table, cache_host=self)

    # ---- static schema inference: compose the internal resize→forward
    #      stages' own inference, so the predicted features layout is the
    #      traced truth (eval_shape through the truncated node) and the
    #      materialized resized image column is modeled too ----

    def infer_schema(self, schema: Any) -> Any:
        from mmlspark_tpu.analysis.info import (
            SchemaError, require_image_input,
        )
        if self.model is None:
            raise SchemaError(
                "model-not-set",
                "ImageFeaturizer has no model bundle; set model=, "
                "set_model_by_name(), or set_model_from_repo() first")
        require_image_input(schema, self.input_col, "ImageFeaturizer")
        for stage in self._stages():
            schema = stage.infer_schema(schema)
        return schema

    # ---- DeviceStage protocol: resize∘forward as one composable op, so
    #      an ImageFeaturizer inside a larger pipeline fuses with its
    #      neighbors. Declines when the resize would actually change the
    #      image dims: transform() also *materializes* the resized image
    #      column, and a fused op that skipped that would diverge from the
    #      stage-by-stage result. ----

    def device_program_token(self):
        bundle = self.model
        return (None if bundle is None else
                (id(bundle.module), bundle.preprocess),
                self.input_col, self.output_col,
                self.cut_output_layers, self.minibatch_size)

    def device_cache_token(self):
        return (self.device_program_token(),
                id(getattr(self.model, "params", None)))

    def device_fingerprint(self):
        """Stable content identity for the persistent AOT compile cache
        (the weights-digest counterpart of ``device_cache_token``)."""
        bundle = self.model
        if bundle is None:
            return None
        from mmlspark_tpu.core.compile_cache import bundle_digest
        return ("ImageFeaturizer", bundle_digest(bundle),
                self.input_col, self.output_col,
                self.cut_output_layers, self.minibatch_size)

    def device_fn(self, meta: ArrayMeta) -> DeviceOp | None:
        bundle: ModelBundle = self.model
        if bundle is None or not meta.is_image or len(meta.shape) != 3:
            return None
        h, w = bundle.input_spec[0], bundle.input_spec[1]
        if tuple(meta.shape[:2]) != (h, w):
            return None  # transform() would rewrite the image column
        rt, jm = self._stages()
        resize_op = rt.device_fn(meta)
        if resize_op is None:
            return None
        fwd_op = jm.device_fn(resize_op.out_meta)
        if fwd_op is None:
            return None

        def fn(params, x):
            return fwd_op.fn(params, resize_op.fn((), x))

        return DeviceOp(fn, fwd_op.out_meta, params=fwd_op.params)
