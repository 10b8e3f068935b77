"""A lone ``JaxModel.transform`` is a segment of one through the planner's
executor (``core/plan.run_entered_segment``): the contracts that hold
because there is one executor, not two.

* the lone call, the serving entry on the same stage
  (``transform_async``, ``min_stages=1``) and the stage second in a fused
  run answer alike, bit for bit, over every column kind
  ``coerce_input_matrix`` accepts;
* reassigned parameters reach the device on the next call with no second
  compile and no growth of the store, on a fused segment too;
* the lone model's program is one the process can count;
* the pre-flight crossing prediction is the executor's own arithmetic;
* a pickled stage carries no cache.
"""

import functools
import gc
import os
import pickle
import sys
import weakref

import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(__file__))

from test_plan import mlp_bundle  # noqa: E402

from mmlspark_tpu import obs
from mmlspark_tpu.analysis.audit import standalone_crossings
from mmlspark_tpu.analysis.info import TableSchema
from mmlspark_tpu.core import plan
from mmlspark_tpu.core.pipeline import PipelineModel
from mmlspark_tpu.core.schema import make_image
from mmlspark_tpu.core.stage import (
    DeviceOp, DeviceStage, HasInputCol, HasOutputCol, Transformer,
)
from mmlspark_tpu.data.table import DataTable, copied_nbytes
from mmlspark_tpu.models.bundle import ModelBundle
from mmlspark_tpu.models.image_featurizer import ImageFeaturizer
from mmlspark_tpu.models.jax_model import JaxModel, coerce_input_matrix
from mmlspark_tpu.obs.runtime import compiled_programs, jit_cache_size

ROWS = 11        # with minibatch 4: two whole minibatches and a padded tail


class Renamed(Transformer, DeviceStage, HasInputCol, HasOutputCol):
    """Test-only identity device stage: the column again under another
    name. It makes a run of two out of any model stage, which reads the
    run's entry column device-resident beside it."""

    def transform(self, table):
        return table.with_column(self.output_col, table[self.input_col])

    def device_fn(self, meta):
        return DeviceOp(lambda params, x: x, meta)


def images(dtype):
    r = np.random.default_rng(3)
    rows = [make_image(f"p{k}", r.integers(0, 255, (6, 5, 3)))
            for k in range(ROWS)]
    if dtype != np.uint8:
        for row in rows:
            row["data"] = row["data"].astype(dtype) / 255.0 - 0.5
    return DataTable({"x": rows})


def tiny_lm_bundle():
    # (the import that puts the checkout's root on the path comes first)
    from test_lm_latent_moe import program_tree, ref, tiny

    from mmlspark_tpu.models import lm

    cfg = tiny()
    module = lm.from_config(cfg, dtype=jnp.bfloat16, logprob_chunk=8)
    tree = jax.tree_util.tree_map(
        lambda a: a.astype(jnp.bfloat16) if a.ndim >= 2 else a,
        program_tree(ref.make_params(cfg, jax.random.PRNGKey(7))))
    return ModelBundle(module=module, params=tree, input_spec=(32,),
                       output_names=module.OUTPUT_NAMES, name="tiny_lm")


def uint8_view():
    block = np.random.default_rng(4).integers(
        0, 255, size=(ROWS, 12)).astype(np.uint8)
    return DataTable({"x": block}), mlp_bundle(12), {}


def float_copied():
    r = np.random.default_rng(5)
    return (DataTable({"x": [r.normal(size=12) for _ in range(ROWS)]}),
            mlp_bundle(12), {})


def scalar_column():
    return (DataTable({"x": np.random.default_rng(6).normal(size=ROWS)}),
            mlp_bundle(1), {})


def token_ids():
    ids = np.random.default_rng(7).integers(
        0, 256, size=(ROWS, 32)).astype(np.int32)
    return (DataTable({"x": ids}), tiny_lm_bundle(),
            dict(output_node="token_logprob", mesh_spec={"dp": 1}))


KINDS = {
    "uint8_image_structs":
        (lambda: (images(np.uint8), mlp_bundle(6 * 5 * 3), {}), True),
    # the planner's strict entry coercion declines a float image column;
    # the lone path's richer one takes it
    "float_image_structs":
        (lambda: (images(np.float32), mlp_bundle(6 * 5 * 3), {}), False),
    "uint8_vectors_a_view": (uint8_view, True),
    "float_vectors_copied": (float_copied, True),
    "scalar_numeric": (scalar_column, True),
    "int32_ids_tiny_lm": (token_ids, True),
}


def scores_of(table):
    col = table["scores"]
    return np.stack(list(col)) if col.dtype == object else np.asarray(col)


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_lone_async_and_fused_answer_alike_bit_for_bit(kind):
    build, plannable = KINDS[kind]
    table, bundle, params = build()
    jm = JaxModel(model=bundle, input_col="x", output_col="scores",
                  minibatch_size=4, **params)
    batch = coerce_input_matrix(table, "x", bundle.input_spec)
    assert batch.shape == (ROWS,) + tuple(bundle.input_spec)
    if kind == "uint8_vectors_a_view":
        assert copied_nbytes(batch) == 0 and batch.dtype == np.uint8
    elif kind != "uint8_image_structs":
        assert copied_nbytes(batch) == batch.nbytes
        assert batch.dtype == np.float32

    with plan.count_crossings() as lone_c:
        lone = scores_of(jm.transform(table))
    assert len(lone) == ROWS and np.isfinite(lone).all()
    # minibatches of 4 on one device, of 8 over the default dp mesh of 8
    assert lone_c.uploads == lone_c.fetches == (3 if params else 2)

    pending = plan.transform_async([jm], table, jm)
    assert pending.dispatched is plannable
    served = scores_of(pending.result())

    stages = [Renamed(input_col="x", output_col="x_again"), jm]
    kinds = [k for k, _ in plan.describe_plan(stages, table)]
    assert kinds == (["device"] if plannable else ["host", "host"])
    with plan.count_crossings() as fused_c:
        fused = scores_of(PipelineModel(stages).transform(table))
    # one upload a minibatch, fused or not
    assert fused_c.uploads == lone_c.uploads

    np.testing.assert_array_equal(served, lone)
    np.testing.assert_array_equal(fused, lone)
    # the three calls went through one store: on the model, the lone call's
    # entry and (where the layouts the two coercions hand over are one
    # layout) the serving call's are one and the same
    assert 1 <= len(jm.__dict__["_plan_cache"]) <= 2


@pytest.mark.parametrize("host", ["pipeline", "featurizer"])
def test_reassigned_params_reach_a_fused_segment_without_a_compile(host):
    if host == "pipeline":
        bundle = mlp_bundle(12)
        table = uint8_view()[0]
        model = PipelineModel([
            Renamed(input_col="x", output_col="x_again"),
            JaxModel(model=bundle, input_col="x", output_col="scores",
                     minibatch_size=4)])
        fresh = functools.partial(JaxModel, model=bundle, input_col="x",
                                  output_col="scores", minibatch_size=4)
    else:
        from test_models import image_table, small_cifar_bundle
        bundle = small_cifar_bundle()
        table = image_table(5).rename({"image": "x"})
        model = ImageFeaturizer(model=bundle, input_col="x",
                                output_col="scores", cut_output_layers=0,
                                minibatch_size=4)
        fresh = functools.partial(ImageFeaturizer, model=bundle,
                                  input_col="x", output_col="scores",
                                  cut_output_layers=0, minibatch_size=4)
    first = scores_of(model.transform(table))
    store = model.__dict__["_plan_cache"]
    (before,) = store.values()
    assert len(before[2][0]) == 2          # a fused run of two stages
    fn = before[1][0]
    obs.enable()
    try:
        compiles = obs.registry().counter("plan.segment_compiles")
        compiled = compiles.value
        for scale in (0.5, 0.25, 0.0):
            bundle.params = jax.tree_util.tree_map(
                lambda p: p * scale, bundle.params)
            second = scores_of(model.transform(table))
        assert compiles.value == compiled
    finally:
        obs.disable()
    (after,) = store.values()
    assert after[1][0] is fn and jit_cache_size(fn) == 1
    assert compiled_programs(model) == 1
    assert not np.allclose(first, second)
    np.testing.assert_array_equal(second, scores_of(fresh().transform(table)))
    # the device tree is the new one, on the old one's shardings
    old = jax.tree_util.tree_leaves(before[1][1])
    new = jax.tree_util.tree_leaves(after[1][1])
    assert [n.sharding for n in new] == [o.sharding for o in old]
    assert all(float(jnp.abs(n).max()) == 0.0 for n in new)


def test_a_tree_of_another_shape_is_a_compile_not_a_reupload():
    bundle = mlp_bundle(12)
    jm = JaxModel(model=bundle, input_col="x", output_col="scores",
                  minibatch_size=4)
    table = uint8_view()[0]
    jm.transform(table)
    (before,) = jm.__dict__["_plan_cache"].values()
    wider = mlp_bundle(12, out_dim=7)
    bundle.module, bundle.params = wider.module, wider.params
    assert scores_of(jm.transform(table)).shape == (ROWS, 7)
    (after,) = jm.__dict__["_plan_cache"].values()
    assert after[1][0] is not before[1][0]


def test_compiled_programs_counts_a_lone_models_program():
    table, bundle, _ = uint8_view()
    jm = JaxModel(model=bundle, input_col="x", output_col="scores",
                  minibatch_size=4)
    assert compiled_programs(jm) == 0
    jm.transform(table)
    jm.transform(table)
    assert compiled_programs(jm) == 1
    # another layout of the column is another program, and is counted
    jm.transform(float_copied()[0])
    assert compiled_programs(jm) == 2


@pytest.mark.parametrize("devices", [1, 8])
def test_standalone_crossings_are_the_executors_own(devices):
    assert jax.local_device_count() == 8
    table, bundle, _ = uint8_view()
    jm = JaxModel(model=bundle, input_col="x", output_col="scores",
                  minibatch_size=4,
                  mesh_spec={"dp": 1} if devices == 1 else None)
    predicted = standalone_crossings(jm, TableSchema.from_table(table),
                                     len(table))
    with plan.count_crossings() as c:
        jm.transform(table)
    # 11 rows: minibatches of 4 on one device, of 8 over the dp mesh of 8
    assert predicted == c.uploads == c.fetches == (3 if devices == 1 else 2)


def test_a_dropped_model_frees_its_program_without_a_cyclic_gc():
    """On the chip a dead model's store held its 22 MB program (and a
    reference to its device parameters) into the next model's window
    until the collector ran (PERF.md, PR 31): the store must not hold
    its own host."""
    table, bundle, _ = uint8_view()
    jm = JaxModel(model=bundle, input_col="x", output_col="scores",
                  minibatch_size=4)
    gc.collect()
    gc.disable()
    try:
        jm.transform(table)
        plan.transform_async([jm], table, jm).result()
        (entry, *_rest) = jm.__dict__["_plan_cache"].values()
        program = weakref.ref(entry[1][0])
        model = weakref.ref(jm)
        del jm, entry, _rest
        assert model() is None and program() is None
    finally:
        gc.enable()


def test_a_pickled_model_that_had_scored_carries_no_cache():
    table, bundle, _ = uint8_view()
    jm = JaxModel(model=bundle, input_col="x", output_col="scores",
                  minibatch_size=4)
    want = scores_of(jm.transform(table))
    assert "_plan_cache" in jm.__dict__ and "_plan_lock" in jm.__dict__
    restored = pickle.loads(pickle.dumps(jm))
    assert not any(k.startswith("_plan") for k in restored.__dict__)
    np.testing.assert_array_equal(scores_of(restored.transform(table)), want)
    assert compiled_programs(restored) == 1
