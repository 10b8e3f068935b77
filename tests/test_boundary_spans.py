"""The boundary tier: the few host spans and counters that are recorded
whether or not the tracer is on (obs/spans.boundary_span), the epoch clock
they can be read on, and ``host_phase_split`` over them.

The contracts under test:

* with the tracer off a boundary span records and a gated span does not;
* the spans of one ``transform`` call share the call's root, and each
  names its parent;
* epoch time is monotone in span time and agrees with ``time.time_ns()``;
* ``host_phase_split`` attributes nested and overlapping spans once, and
  what no phase covers is ``unspanned``;
* a tiny ``transform`` and a tiny ``fit_stream`` leave the documented
  spans, whose shares close to the wall;
* uploaded bytes over rows handed back is the row's width.
"""

import os
import sys
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))

from test_plan import mlp_bundle  # noqa: E402

from mmlspark_tpu import obs
from mmlspark_tpu.data.table import DataTable
from mmlspark_tpu.models.jax_model import JaxModel
from mmlspark_tpu.models.zoo import MLP
from mmlspark_tpu.obs import runtime as obs_rt
from mmlspark_tpu.obs.events import SpanRecord
from mmlspark_tpu.train import TrainConfig, Trainer

TRANSFORM_SPANS = {"transform", "transform/coerce", "plan/dispatch",
                   "plan/h2d", "plan/d2h", "transform/assemble"}


@pytest.fixture(autouse=True)
def tracer_off_and_clean():
    obs.disable()
    obs.clear()
    obs.registry().reset()
    yield
    obs.disable()
    obs.clear()
    obs.registry().reset()


def uint8_table(rows=24, width=6, seed=0):
    rng = np.random.default_rng(seed)
    return DataTable({"x": list(rng.integers(0, 255, size=(rows, width))
                                .astype(np.uint8))})


def scored(rows=24, width=6, minibatch=8):
    jm = JaxModel(model=mlp_bundle(width), input_col="x",
                  output_col="scores", minibatch_size=minibatch)
    table = uint8_table(rows, width)
    jm.transform(table)          # compile
    obs.clear()
    obs.registry().reset()
    jm.transform(table)
    return obs.captured()


def rec(name, start_ms, dur_ms, tid=1):
    return SpanRecord(name, "plan", int(start_ms * 1e6), int(dur_ms * 1e6),
                      tid, f"t{tid}", 0, None, 0, None)


# ---- the tier itself ----

def test_boundary_span_records_with_the_tracer_off_and_a_gated_one_not():
    assert not obs.enabled()
    with obs.span("gated", "t"):
        with obs.boundary_span("edge", "t", rows=3, nbytes=12):
            pass
    (r,) = obs.captured()
    assert (r.name, r.rows, r.nbytes, r.labels) == ("edge", 3, 12, None)
    assert r.root_id == r.span_id and r.parent_id is None
    assert r.to_dict()["root_id"] == r.span_id
    # a gated span, once enabled, records without the tier's fields
    obs.enable()
    with obs.span("gated", "t"):
        pass
    gated = obs.captured()[-1]
    assert gated.name == "gated" and gated.root_id is None
    assert "root_id" not in gated.to_dict()


def test_one_transform_call_shares_its_root_and_names_parents():
    records = scored()
    assert {r.name for r in records} == TRANSFORM_SPANS
    (root,) = [r for r in records if r.name == "transform"]
    assert (root.rows, root.minibatches) == (24, 3)
    assert all(r.root_id == root.span_id for r in records)
    by_id = {r.span_id: r for r in records}
    for r in records:
        if r is root:
            assert r.parent_id is None
            continue
        parent = by_id[r.parent_id]
        assert parent.start_ns <= r.start_ns and r.end_ns <= parent.end_ns
        want = "plan/dispatch" if r.name == "plan/h2d" else "transform"
        assert parent.name == want
    # a second call gets a root of its own
    assert len({r.root_id for r in scored() + records}) == 2


def test_a_root_that_raises_frees_the_thread_for_the_next_call():
    with pytest.raises(RuntimeError):
        with obs.boundary_span("transform", "plan"):
            raise RuntimeError("boom")
    with obs.boundary_span("transform", "plan"):
        pass
    first, second = obs.captured()
    assert first.root_id == first.span_id
    assert second.root_id == second.span_id != first.span_id


# ---- one clock with the device trace ----

def test_epoch_time_is_monotone_and_matches_time_ns_within_a_millisecond():
    before = time.time_ns()
    with obs.boundary_span("a", "t"):
        time.sleep(0.002)
    with obs.boundary_span("b", "t"):
        pass
    after = time.time_ns()
    a, b = obs.captured()
    assert a.start_epoch_ns < a.end_epoch_ns <= b.start_epoch_ns
    assert a.end_epoch_ns - a.start_epoch_ns == a.dur_ns
    assert before - 1_000_000 <= a.start_epoch_ns
    assert b.end_epoch_ns <= after + 1_000_000
    assert obs_rt.to_epoch_ns(time.perf_counter_ns()) == pytest.approx(
        time.time_ns(), abs=1_000_000)
    trace = obs.chrome_trace([a])
    (ev,) = [e for e in trace["traceEvents"] if e["ph"] == "X"]
    assert ev["ts"] * 1e3 + trace["otherData"]["epoch_offset_ns"] == \
        pytest.approx(a.start_epoch_ns, abs=1_000)


# ---- host_phase_split on synthetic records ----

@pytest.mark.parametrize("records, wall_s, want", [
    # h2d nested in dispatch; d2h after; a gap nobody spans
    ([rec("transform", 0, 20), rec("plan/dispatch", 2, 6),
      rec("plan/h2d", 3, 4), rec("plan/d2h", 10, 5)], None,
     {"wall_s": 0.020, "h2d_s": 0.004, "dispatch_s": 0.002,
      "fetch_wait_s": 0.005, "unspanned_s": 0.009}),
    # a d2h that begins inside a dispatch counts only outside it
    ([rec("plan/dispatch", 0, 10, 1), rec("plan/d2h", 8, 6, 2)], None,
     {"wall_s": 0.014, "dispatch_s": 0.010, "fetch_wait_s": 0.004,
      "unspanned_s": 0.0}),
    # coerce and assemble around the plan spans, a caller's wider wall
    ([rec("transform", 0, 10), rec("transform/coerce", 0, 4),
      rec("plan/dispatch", 4, 1), rec("plan/d2h", 5, 3),
      rec("transform/assemble", 8, 1), rec("transform/assemble", 9, 1)],
     0.0125,
     {"wall_s": 0.0125, "coerce_s": 0.004, "dispatch_s": 0.001,
      "fetch_wait_s": 0.003, "assemble_s": 0.002, "unspanned_s": 0.0025}),
    # the train loop's two
    ([rec("train/step", 0, 2), rec("train/loss_fetch", 2, 6),
      rec("train/step", 9, 1)], None,
     {"wall_s": 0.010, "step_dispatch_s": 0.003, "loss_fetch_s": 0.006,
      "unspanned_s": 0.001}),
], ids=["nested", "overlap_across_threads", "wall_given", "train"])
def test_host_phase_split_attributes_each_instant_once(records, wall_s, want):
    split = obs.host_phase_split(records, wall_s=wall_s)
    for key, value in want.items():
        assert split[key] == pytest.approx(value), key
    seconds = sum(v for k, v in split.items()
                  if k.endswith("_s") and k != "wall_s")
    assert seconds == pytest.approx(split["wall_s"])
    assert sum(v for k, v in split.items()
               if k.endswith("_share")) == pytest.approx(1.0)
    assert not any("compute" in k or "idle" in k for k in split)


def test_host_phase_split_ignores_what_is_not_a_boundary_name():
    assert obs.host_phase_split([rec("serve/pack", 0, 5)]) is None
    split = obs.host_phase_split([rec("serve/pack", 0, 50),
                                  rec("plan/h2d", 10, 5)])
    assert split["wall_s"] == pytest.approx(0.005)


# ---- the program's own paths, tracer off ----

def test_tiny_transform_leaves_the_spans_and_its_shares_close():
    assert not obs.enabled()
    records = scored()
    split = obs.host_phase_split(records)
    (root,) = [r for r in records if r.name == "transform"]
    assert split["wall_s"] == pytest.approx(root.dur_ns / 1e9)
    for phase in ("coerce", "h2d", "dispatch", "fetch_wait", "assemble"):
        assert split[f"{phase}_s"] > 0, phase
    assert sum(v for k, v in split.items()
               if k.endswith("_share")) == pytest.approx(1.0)
    assert split["unspanned_share"] < 0.5


def test_h2d_bytes_per_row_is_the_row_width_on_a_uint8_table():
    records = scored(rows=24, width=6, minibatch=8)
    sent = sum(r.nbytes for r in records if r.name == "plan/h2d")
    rows = sum(r.rows for r in records if r.name == "transform")
    assert sent / rows == 6
    counters = obs.registry().snapshot()["counters"]
    assert counters["plan.h2d_bytes"] / counters["transform.rows"] == 6
    assert counters["plan.h2d_uploads"] == 3
    assert counters["plan.d2h_bytes"] == 24 * 4 * 4   # 4 float32 logits
    # the gated series stay off with the tracer
    assert not any(k.startswith(("plan.h2d_shapes", "plan.d2h_fetches"))
                   for k in counters)


@pytest.mark.parametrize("fit", ["fit_stream", "fit_arrays"])
def test_tiny_fit_leaves_step_and_loss_fetch_spans(fit):
    assert not obs.enabled()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 6)).astype(np.float32)
    y = rng.integers(0, 3, size=64)
    trainer = Trainer(MLP(features=(8,), num_outputs=3),
                      TrainConfig(batch_size=8, epochs=1, log_every=2,
                                  mesh_spec={"dp": 1}))
    if fit == "fit_stream":
        trainer.fit_stream(iter([(x, y)]))
    else:
        trainer.fit_arrays(x, y)
    main = [r for r in obs.captured() if r.name.startswith("train/")]
    assert {r.name for r in main} == {"train/step", "train/loss_fetch"}
    assert sum(r.name == "train/step" for r in main) == 8
    # log points at steps 1, 3, 5, 7: three lagged fetches and the last
    assert sum(r.name == "train/loss_fetch" for r in main) == 4
    assert len(trainer.history) == 4
    split = obs.host_phase_split(main)
    assert split["step_dispatch_s"] > 0 and split["loss_fetch_s"] > 0
    assert (split["step_dispatch_share"] + split["loss_fetch_share"]
            + split["unspanned_share"]) == pytest.approx(1.0)
    # the loader's uploads are boundary records of its own thread, under
    # no root of the loop's
    h2d = [r for r in obs.captured() if r.name == "plan/h2d"]
    assert len(h2d) == 8 * 3 and all(r.root_id == r.span_id for r in h2d)
