"""``DataTable.column_matrix``: a column that is already a matrix is handed
back, not copied.

The contracts under test:

* an object column whose rows are, in order, the consecutive rows of one
  contiguous block comes back as a read-only view of that block (a table
  built from a matrix, from a slice of one, from ``[N,H,W,C]``, a
  contiguous sub-range of a table, the column ``JaxModel.transform`` wrote);
* anything else (a row replaced, rows reversed / permuted / filtered, a
  strided source, separate allocations, a list among the rows) is stacked
  into an owned, writable array with order and values kept;
* a dtype that differs is one owned, writable conversion of the block;
* the view refuses writes and the user's array stays writable;
* ``JaxModel.transform`` and a fused segment give bit-identical outputs by
  both paths, the padded tail included;
* counters ``table.matrix_rows_viewed`` / ``table.matrix_rows_copied`` and
  ``rows`` / ``nbytes`` of the ``transform/coerce`` record say what happened.
"""

import os
import sys

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))

from test_plan import mlp_bundle  # noqa: E402

from mmlspark_tpu import obs
from mmlspark_tpu.core.pipeline import PipelineModel
from mmlspark_tpu.data.table import DataTable, copied_nbytes
from mmlspark_tpu.models.jax_model import JaxModel, coerce_input_matrix

ROWS, WIDTH = 60, 6


@pytest.fixture(autouse=True)
def clean_ring_and_registry():
    obs.disable()
    obs.clear()
    obs.registry().reset()
    yield
    obs.clear()
    obs.registry().reset()


def matrix(dtype=np.uint8, rows=ROWS, width=WIDTH, seed=0):
    rng = np.random.default_rng(seed)
    return rng.integers(0, 255, size=(rows, width)).astype(dtype)


def counters():
    reg = obs.registry()
    return (int(reg.counter("table.matrix_rows_viewed").value),
            int(reg.counter("table.matrix_rows_copied").value))


# ---- the block is handed back ----

def _whole(dtype):
    data = matrix(dtype)
    return DataTable({"x": data}), data, data


def _sliced():
    data = matrix(np.uint8)
    return DataTable({"x": data[5:50]}), data, data[5:50]


def _images():
    data = matrix(np.uint8, width=4 * 3 * 2).reshape(ROWS, 4, 3, 2)
    return DataTable({"x": data}), data, data.reshape(ROWS, -1)


def _sub_range(how):
    data = matrix(np.float32)
    table = DataTable({"x": data, "k": np.arange(ROWS)})
    if how == "head":
        return table.head(17), data, data[:17]
    if how == "partition":
        part = table.partitions(4)[2]
        return part, data, data[part["k"][0]:part["k"][-1] + 1]
    return table.take(np.arange(20, 41)), data, data[20:41]


def _one_row():
    data = matrix(np.float32)
    return DataTable({"x": data[7:8]}), data, data[7:8]


VIEWED = {
    "uint8": lambda: _whole(np.uint8),
    "float32": lambda: _whole(np.float32),
    "int64": lambda: _whole(np.int64),
    "sliced source": _sliced,
    "rows of [N,H,W,C]": _images,
    "head of a table": lambda: _sub_range("head"),
    "partition of a table": lambda: _sub_range("partition"),
    "contiguous take": lambda: _sub_range("take"),
    "a single row": _one_row,
}


@pytest.mark.parametrize("case", sorted(VIEWED))
def test_a_column_that_is_one_block_is_handed_back(case):
    table, source, want = VIEWED[case]()
    got = table.column_matrix("x", dtype=source.dtype)
    assert np.shares_memory(got, source)
    assert got.shape == want.shape and got.dtype == source.dtype
    np.testing.assert_array_equal(got, want)
    assert got.flags.c_contiguous and not got.flags.writeable
    assert copied_nbytes(got) == 0
    assert counters() == (len(table), 0)


# ---- a copy is made, order and values kept ----

def _replaced():
    data = matrix()
    table = DataTable({"x": data})
    table["x"][3] = data[3].copy()       # equal, but allocated apart
    return table, data, data


def _selection(indices):
    data = matrix()
    return DataTable({"x": data}).take(indices), data, data[indices]


def _strided():
    data = matrix(width=2 * WIDTH)
    return DataTable({"x": data[:, ::2]}), data, data[:, ::2]


def _separate():
    data = matrix()
    return DataTable({"x": [row.copy() for row in data]}), data, data


def _a_list_among_them():
    data = matrix()
    table = DataTable({"x": data})
    table["x"][ROWS - 1] = data[ROWS - 1].tolist()
    return table, data, data


def _fortran_order():
    data = np.asfortranarray(matrix())
    return DataTable({"x": data}), data, data


def _every_other_row():
    data = matrix()
    return DataTable({"x": data[::2]}), data, data[::2]


def _one_row_of_another_dtype():
    data = matrix()
    table = DataTable({"x": data})
    table["x"][0] = data[0].astype(np.int16)
    return table, data, data


def _a_row_twice():
    data = matrix()
    idx = np.r_[0:10, 9:ROWS - 1]        # row 9 twice: right length, a hole
    return DataTable({"x": data}).take(idx), data, data[idx]


COPIED = {
    "one row replaced by an equal array": _replaced,
    "reversed": lambda: _selection(np.arange(ROWS)[::-1]),
    "permuted": lambda: _selection(np.random.default_rng(1).permutation(ROWS)),
    "filtered": lambda: _selection(np.arange(ROWS) % 3 != 0),
    "strided source": _strided,
    "separate allocations": _separate,
    "a list among the rows": _a_list_among_them,
    "fortran-ordered source": _fortran_order,
    "every other row": _every_other_row,
    "one row of another dtype": _one_row_of_another_dtype,
    "a row taken twice": _a_row_twice,
}


@pytest.mark.parametrize("case", sorted(COPIED))
def test_anything_else_is_stacked_into_an_owned_copy(case):
    table, source, want = COPIED[case]()
    got = table.column_matrix("x", dtype=np.uint8)
    assert not np.shares_memory(got, source)
    assert got.dtype == np.uint8 and got.flags.writeable
    np.testing.assert_array_equal(got, want)
    got[0, 0] ^= 0xFF                       # private: the table is untouched
    np.testing.assert_array_equal(
        np.stack([np.asarray(v) for v in table["x"]]), want)
    assert copied_nbytes(got) == got.nbytes
    assert counters() == (0, len(table))


def test_a_row_reassigned_after_construction_is_seen_on_the_next_call():
    data = matrix()
    table = DataTable({"x": data})
    assert np.shares_memory(table.column_matrix("x", np.uint8), data)
    table["x"][10] = np.full(WIDTH, 7, np.uint8)
    got = table.column_matrix("x", np.uint8)
    assert not np.shares_memory(got, data)
    assert (got[10] == 7).all() and (got[11] == data[11]).all()
    table["x"][10] = data[10]               # the view put back: a block again
    assert np.shares_memory(table.column_matrix("x", np.uint8), data)


@pytest.mark.parametrize("source, want", [
    (np.uint8, np.float32), (np.float32, np.float64),
    (np.float32, np.uint8), (np.int64, np.float32)])
def test_another_dtype_is_one_owned_conversion_of_the_block(source, want):
    data = matrix(source) if source != np.float32 else \
        matrix(np.float32) * np.float32(1.37)
    table = DataTable({"x": data})
    got = table.column_matrix("x", dtype=want)
    assert got.dtype == want and got.flags.writeable and got.flags.owndata
    assert not np.shares_memory(got, data)
    np.testing.assert_array_equal(
        got, np.stack(list(table["x"])).astype(want))
    assert counters() == (0, ROWS)


def test_the_view_refuses_writes_and_the_users_array_stays_writable():
    data = matrix(np.float32)
    before = data.copy()
    view = DataTable({"x": data}).column_matrix("x")
    with pytest.raises(ValueError, match="read-only"):
        view[0, 0] = 1.0
    with pytest.raises(ValueError, match="read-only"):
        view *= 2.0
    with pytest.raises(ValueError, match="read-only"):
        np.random.default_rng(0).shuffle(view)
    with pytest.raises(ValueError):
        view.flags.writeable = True         # not ours to take back
    assert data.flags.writeable and data[0].flags.writeable
    data[0, 0] += 1.0                       # the user's array is theirs
    assert view[0, 0] == before[0, 0] + 1.0  # and the view aliases it


def test_numeric_and_empty_columns_are_as_they_were():
    table = DataTable({"s": np.arange(5, dtype=np.int32)})
    got = table.column_matrix("s")
    assert got.shape == (5, 1) and got.dtype == np.float32
    assert got.flags.writeable and counters() == (0, 0)
    empty = DataTable({"x": np.empty(0, dtype=object)})
    assert empty.column_matrix("x").shape == (0, 0)
    assert counters() == (0, 0)


# ---- through the model: the same answers, and the record of it ----

def model(width=WIDTH, minibatch=16, out_dim=4, **cols):
    cols = {"input_col": "x", "output_col": "scores", **cols}
    return JaxModel(model=mlp_bundle(width, out_dim=out_dim),
                    minibatch_size=minibatch, mesh_spec={"dp": 1}, **cols)


def coerce_records():
    return [(r.rows, r.nbytes) for r in obs.captured()
            if getattr(r, "name", None) == "transform/coerce"]


@pytest.mark.parametrize("rows", [48, 37, 5])
def test_transform_gives_the_same_bits_by_both_paths(rows):
    # 37 and 5 are no multiple of the minibatch of 16: the padded tail
    data = matrix(rows=rows)
    block = DataTable({"x": data})
    apart = DataTable({"x": [row.copy() for row in data]})
    jm = model()
    jm.transform(block)                     # compile
    obs.clear()
    obs.registry().reset()
    by_view = jm.transform(block)
    assert coerce_records() == [(rows, 0)]
    assert counters() == (rows, 0)
    obs.clear()
    obs.registry().reset()
    by_copy = jm.transform(apart)
    assert coerce_records() == [(rows, rows * WIDTH)]
    assert counters() == (0, rows)
    a, b = (np.stack(list(t["scores"])) for t in (by_view, by_copy))
    assert a.shape == (rows, 4)
    assert a.tobytes() == b.tobytes()
    assert data.flags.writeable             # the upload took nothing away


def test_a_float64_source_is_converted_once_and_counted_as_copied():
    data = matrix(np.float64)
    jm = model()
    out = jm.transform(DataTable({"x": data}))
    assert coerce_records() == [(ROWS, ROWS * WIDTH * 4)]   # float32 made
    assert counters() == (0, ROWS)
    ref = jm.transform(DataTable({"x": [r.astype(np.float32)
                                        for r in data]}))
    np.testing.assert_array_equal(np.stack(list(out["scores"])),
                                  np.stack(list(ref["scores"])))


def test_the_column_transform_wrote_is_handed_to_the_next_reader():
    featurizer = model(output_col="features", out_dim=5)
    feats = featurizer.transform(DataTable({"x": matrix()}))
    obs.registry().reset()
    got = feats.column_matrix("features")
    assert counters() == (ROWS, 0)
    assert np.shares_memory(got, feats["features"][0])
    assert got.shape == (ROWS, 5) and not got.flags.writeable
    # featurizer -> the next model: its coercion copies nothing either
    obs.clear()
    head = model(width=5, input_col="features", output_col="y")
    scored = head.transform(feats)
    assert coerce_records() == [(ROWS, 0)]
    np.testing.assert_array_equal(
        np.stack(list(scored["y"])),
        np.stack(list(head.transform(DataTable(
            {"features": [r.copy() for r in feats["features"]]}))["y"])))


def test_coerce_input_matrix_reshapes_the_view_without_copying():
    data = matrix(np.uint8, width=4 * 3 * 2)
    got = coerce_input_matrix(DataTable({"x": data}), "x", (4, 3, 2))
    assert got.shape == (ROWS, 4, 3, 2)
    assert np.shares_memory(got, data) and copied_nbytes(got) == 0


@pytest.mark.parametrize("apart", [False, True])
def test_a_fused_segment_carries_the_same_record(apart):
    data = matrix(rows=21)
    rows = [r.copy() for r in data] if apart else data
    stages = [model(output_col="f", out_dim=5, minibatch=8),
              model(width=5, input_col="f", output_col="g", minibatch=8)]
    ref = stages[1].transform(stages[0].transform(DataTable({"x": rows})))
    obs.clear()
    fused = PipelineModel(stages).transform(DataTable({"x": rows}))
    assert coerce_records() == [(21, 21 * WIDTH if apart else 0)]
    np.testing.assert_array_equal(np.stack(list(fused["g"])),
                                  np.stack(list(ref["g"])))


def test_learners_fit_on_the_view_and_leave_the_table_alone():
    from mmlspark_tpu.ml.learners import LogisticRegression
    from mmlspark_tpu.ml.train_classifier import TrainClassifier

    data = matrix(np.float32, rows=40) / np.float32(255)
    before = data.copy()
    labels = (data[:, 0] > 0.5).astype(np.int64)
    table = DataTable({"features": data, "label": labels})
    fitted = TrainClassifier(model=LogisticRegression(),
                             label_col="label").fit(table)
    scored = fitted.transform(table)
    assert len(scored) == 40
    np.testing.assert_array_equal(data, before)
    assert data.flags.writeable
