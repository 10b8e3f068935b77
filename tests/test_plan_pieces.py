"""A minibatch whose upload is long goes to the device in row pieces
(``core/plan.piece_rows``): batch execution alone, decided by the bytes of
the minibatch against one module constant, which these tests patch down
through the module attribute for their duration.

The contracts under test:

* a cut minibatch's rows come back once each and in order, with a padded
  tail and with a table shorter than one minibatch, at dp 1 and over the
  8-device CPU mesh; a call uploads one entry shape;
* the answers are the uncut run's;
* a minibatch under the constant is one upload of the shape it always had;
* the serving entry (``dispatch_segment``) never cuts;
* the pre-flight prediction is the uploads the executor made, cut and
  uncut, for a lone model and for a fused run;
* ``plan.split_minibatches`` and the ``transform`` record's
  ``minibatches`` say what the call did;
* the user's ``max_inflight`` stays a bound in minibatches' worth.
"""

import os
import sys

import flax.linen as nn
import numpy as np
import pytest

import jax
import jax.numpy as jnp

sys.path.insert(0, os.path.dirname(__file__))

from test_lone_segment import Renamed  # noqa: E402

from mmlspark_tpu import obs
from mmlspark_tpu.analysis.audit import standalone_crossings
from mmlspark_tpu.analysis.info import TableSchema
from mmlspark_tpu.core import plan
from mmlspark_tpu.core.pipeline import PipelineModel
from mmlspark_tpu.data.table import DataTable
from mmlspark_tpu.models.bundle import ModelBundle
from mmlspark_tpu.models.jax_model import JaxModel
from mmlspark_tpu.models.resnet import ResNet

WIDTH = 12          # uint8 bytes a row
MINIBATCH = 32
CUT = 8 * WIDTH     # the constant patched down: pieces of at most 8 rows


class Scale(nn.Module):
    """Elementwise: every answer is one product, whatever the batch."""

    OUTPUT_NAMES = ("scaled",)

    @nn.compact
    def __call__(self, x, output: str = "scaled", train: bool = False):
        w = self.param("w", lambda _key, shape: jnp.linspace(
            0.5, 2.0, shape[0]), (x.shape[-1],))
        return x.astype(jnp.float32) * w


def bundle_of(module, spec):
    params = module.init(jax.random.PRNGKey(0),
                         np.zeros((1,) + spec, np.float32))["params"]
    return ModelBundle(module=module,
                       params=jax.tree_util.tree_map(np.asarray, params),
                       input_spec=spec,
                       output_names=type(module).OUTPUT_NAMES)


def scale_model(dp, **params):
    return JaxModel(model=bundle_of(Scale(), (WIDTH,)), input_col="x",
                    output_col="y", minibatch_size=MINIBATCH,
                    mesh_spec={"dp": 1} if dp == 1 else None, **params)


def numbered(rows, width=WIDTH):
    """Row i holds i in every byte but the first, which holds 255 - i: a
    row out of place, missing or doubled shows in the answers."""
    block = np.repeat(np.arange(rows, dtype=np.uint8)[:, None], width, 1)
    block[:, 0] = 255 - block[:, 0]
    return DataTable({"x": block}), block


def answers(table, col="y"):
    return np.stack(table[col])


@pytest.fixture()
def cut(monkeypatch):
    monkeypatch.setattr(plan, "_PIECE_MAX_BYTES", CUT)


@pytest.fixture(autouse=True)
def clean_ring():
    obs.clear()
    obs.registry().reset()
    yield
    obs.clear()
    obs.registry().reset()


def split_count():
    return obs.registry().snapshot()["counters"].get(
        "plan.split_minibatches", 0)


# rows, dp -> the uploads of a cut call, their one shape, the user's
# minibatches, and how many of those were cut. 75 rows end in a padded
# piece; 20 and 5 are shorter than one minibatch (a size of 20 halves to
# 5; 5 is odd and goes whole). Over the mesh of 8 a piece is 8 rows, one a
# device, and a minibatch of 24 (20 rounded up) has no half that divides
CASES = [
    (75, 1, 10, (8, WIDTH), 3, 3),
    (64, 1, 8, (8, WIDTH), 2, 2),
    (20, 1, 4, (5, WIDTH), 1, 1),
    (5, 1, 1, (5, WIDTH), 1, 0),
    (75, 8, 10, (8, WIDTH), 3, 3),
    (64, 8, 8, (8, WIDTH), 2, 2),
    (20, 8, 1, (24, WIDTH), 1, 0),
    (5, 8, 1, (8, WIDTH), 1, 0),
]


@pytest.mark.parametrize("rows, dp, uploads, shape, minibatches, split",
                         CASES)
def test_a_cut_call_hands_every_row_back_once_and_in_order(
        cut, rows, dp, uploads, shape, minibatches, split):
    assert jax.local_device_count() == 8
    table, block = numbered(rows)
    jm = scale_model(dp)
    predicted = standalone_crossings(jm, TableSchema.from_table(table),
                                     rows)
    with plan.count_crossings() as c:
        out = answers(jm.transform(table))
    np.testing.assert_array_equal(
        out, block.astype(np.float32) * jm.model.params["w"])
    assert c.uploads == c.fetches == predicted == uploads
    assert c.upload_shapes == {shape}        # one entry shape a call
    assert split_count() == split
    (root,) = [r for r in obs.captured() if r.name == "transform"]
    assert root.minibatches == minibatches and root.rows == rows
    sent = [r for r in obs.captured() if r.name == "plan/h2d"]
    assert len(sent) == uploads
    assert {r.root_id for r in sent} == {root.span_id}


def conv_model():
    module = ResNet(num_classes=5, stage_sizes=(1,), width=8,
                    dtype=jnp.float32, norm="none", stem="direct")
    return JaxModel(model=bundle_of(module, (8, 8, 3)), input_col="x",
                    output_col="y", minibatch_size=MINIBATCH,
                    mesh_spec={"dp": 1})


@pytest.mark.parametrize("kind", ["elementwise", "conv"])
def test_the_answers_are_the_uncut_runs(monkeypatch, kind):
    if kind == "elementwise":
        jm, (table, _) = scale_model(1), numbered(75)
    else:
        jm = conv_model()
        table = DataTable({"x": np.random.default_rng(2).integers(
            0, 255, size=(75, 8 * 8 * 3)).astype(np.uint8)})
    with plan.count_crossings() as whole_c:
        whole = answers(jm.transform(table))
    monkeypatch.setattr(plan, "_PIECE_MAX_BYTES", 8 * table["x"][0].nbytes)
    with plan.count_crossings() as cut_c:
        pieces = answers(jm.transform(table))
    assert (whole_c.uploads, cut_c.uploads) == (3, 10)
    if kind == "elementwise":
        np.testing.assert_array_equal(pieces, whole)
    else:
        # a convolution and a product sum in an order the compiler picks
        # for the batch's shape, so another entry shape may round the last
        # bits of a float32 differently
        np.testing.assert_allclose(pieces, whole, rtol=1e-4, atol=1e-4)


def test_a_minibatch_under_the_constant_is_one_upload_of_its_shape():
    assert MINIBATCH * WIDTH < plan._PIECE_MAX_BYTES
    table, _ = numbered(75)
    jm = scale_model(1)
    with plan.count_crossings() as c:
        jm.transform(table)
    assert c.uploads == 3 and c.upload_shapes == {(MINIBATCH, WIDTH)}
    assert split_count() == 0


@pytest.mark.parametrize("minibatch, row_nbytes, dp, rows", [
    (2048, 150_528, 1, None),    # the ResNet score cell: set below
    (2, 4 * 4096, 1, 2),         # an LM cell's minibatch: 32 KB, whole
    (16, 1, 1, 16),
    (104, 1 << 30, 8, 104),      # no half of 104 divides over 8
    (96, 1 << 30, 8, 24),        # halves while the half divides: 48, 24
    (64, 1 << 30, 1, 1),         # a row over the constant: one a piece
])
def test_piece_rows_is_a_power_of_two_fraction_that_divides_over_dp(
        minibatch, row_nbytes, dp, rows):
    got = plan.piece_rows(minibatch, row_nbytes, dp)
    if rows is None:
        assert got * row_nbytes <= plan._PIECE_MAX_BYTES < 2 * got * row_nbytes
    else:
        assert got == rows
    assert got % dp == 0 and minibatch % got == 0
    assert (minibatch // got) & (minibatch // got - 1) == 0


def test_the_serving_entry_never_cuts(cut):
    table, block = numbered(75)
    jm = scale_model(1)
    with plan.count_crossings() as c:
        pending = plan.transform_async([jm], table, jm)
        served = answers(pending.result())
    # a packed batch over the stage's bound is chunked at that bound, as
    # it always was: the shapes a server compiles stay the ladder's
    assert pending.shapes == ((MINIBATCH, WIDTH),) * 3
    assert c.uploads == 3 and split_count() == 0
    with plan.count_crossings() as c:
        np.testing.assert_array_equal(answers(jm.transform(table)), served)
    assert c.upload_shapes == {(8, WIDTH)}


@pytest.mark.parametrize("dp", [1, 8])
@pytest.mark.parametrize("is_cut", [False, True])
def test_a_fused_runs_prediction_is_its_uploads(monkeypatch, dp, is_cut):
    if is_cut:
        monkeypatch.setattr(plan, "_PIECE_MAX_BYTES", CUT)
    table, block = numbered(75)
    stages = [Renamed(input_col="x", output_col="x_again"), scale_model(dp)]
    stages[1].set(input_col="x_again")
    seg = plan.collect_segment(stages, 0,
                               lambda col: plan._entry_meta(table, col))
    assert seg is not None and len(seg.stages) == 2
    with plan.count_crossings() as c:
        out = PipelineModel(stages).transform(table)
    assert plan.predict_segment_minibatches(seg, 75) == c.uploads == (
        10 if is_cut else 3)
    np.testing.assert_array_equal(
        answers(out),
        block.astype(np.float32) * stages[1].model.params["w"])


def test_max_inflight_stays_a_bound_in_minibatches_worth(cut, monkeypatch):
    seen = []
    real = plan._windowed_dispatch

    def recording(fn, dev_params, batch, size, target, max_inflight,
                  **kwargs):
        seen.append((size, max_inflight))
        return real(fn, dev_params, batch, size, target, max_inflight,
                    **kwargs)

    monkeypatch.setattr(plan, "_windowed_dispatch", recording)
    table, _ = numbered(75)
    scale_model(1, max_inflight=3).transform(table)
    # 3 minibatches of 32 rows are 12 pieces of 8: never more rows' answers
    # on the device than the user allowed
    assert seen == [(8, 12)]
