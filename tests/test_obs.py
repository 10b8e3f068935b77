"""Obs subsystem suite: span tracer correctness (nesting, threads,
enable/disable isolation), histogram percentiles vs numpy, Chrome-trace
export validity, and the acceptance contract that obs counters EXACTLY
equal the independently observed crossing/compile values the PR 1/PR 4
tests assert at the planner's own seams — one telemetry substrate, not a
second set of numbers."""

import json
import os
import sys
import threading

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))

from test_plan import image_table, mlp_bundle  # noqa: E402

from mmlspark_tpu import obs
from mmlspark_tpu.core import plan
from mmlspark_tpu.core.pipeline import PipelineModel
from mmlspark_tpu.core.schema import make_image
from mmlspark_tpu.data.table import DataTable
from mmlspark_tpu.models.jax_model import JaxModel
from mmlspark_tpu.obs.events import SpanRecord
from mmlspark_tpu.stages.featurize import AssembleFeatures
from mmlspark_tpu.stages.image import ImageTransformer, UnrollImage


@pytest.fixture(autouse=True)
def obs_isolated():
    """Every test starts AND ends with the tracer off and all state
    dropped — enabling obs in one test must never leak spans, counters,
    or the enabled flag into the next (the flag-isolation contract)."""
    obs.disable()
    obs.clear()
    obs.registry().reset()
    yield
    obs.disable()
    obs.clear()
    obs.registry().reset()


# ---- span tracer ----

def test_disabled_span_is_shared_null_and_records_nothing():
    assert not obs.enabled()
    s1, s2 = obs.span("a"), obs.span("b", "cat", {"k": 1})
    assert s1 is s2  # one shared null context: no allocation when off
    with s1:
        pass
    obs.event("instant")
    assert obs.captured() == []


def test_nested_spans_record_parentage_and_containment():
    obs.enable()
    with obs.span("outer", "t"):
        with obs.span("mid", "t"):
            with obs.span("inner", "t", {"k": "v"}):
                pass
        with obs.span("mid2", "t"):
            pass
    recs = {r.name: r for r in obs.captured()}
    assert set(recs) == {"outer", "mid", "inner", "mid2"}
    outer, mid, inner, mid2 = (recs[n]
                               for n in ("outer", "mid", "inner", "mid2"))
    assert outer.parent_id is None and outer.depth == 0
    assert mid.parent_id == outer.span_id and mid.depth == 1
    assert inner.parent_id == mid.span_id and inner.depth == 2
    assert mid2.parent_id == outer.span_id and mid2.depth == 1
    assert inner.labels == {"k": "v"}
    # wall-clock containment: children lie inside their parent
    for child, parent in ((mid, outer), (inner, mid), (mid2, outer)):
        assert child.start_ns >= parent.start_ns
        assert child.end_ns <= parent.end_ns
    # siblings are ordered, not overlapping
    assert mid.end_ns <= mid2.start_ns


def test_span_records_survive_exceptions():
    obs.enable()
    with pytest.raises(ValueError):
        with obs.span("dies", "t"):
            raise ValueError("boom")
    (rec,) = obs.captured()
    assert rec.name == "dies" and rec.dur_ns >= 0
    # the thread-local stack unwound: a new root span has no parent
    with obs.span("next", "t"):
        pass
    assert [r.parent_id for r in obs.captured()] == [None, None]


def test_threaded_spans_keep_independent_stacks():
    obs.enable()
    barrier = threading.Barrier(2)

    def work(tag: str) -> None:
        barrier.wait()
        with obs.span(f"{tag}/outer", "t"):
            with obs.span(f"{tag}/inner", "t"):
                pass

    threads = [threading.Thread(target=work, args=(t,), name=f"W{t}")
               for t in ("a", "b")]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    recs = {r.name: r for r in obs.captured()}
    assert len(recs) == 4
    for tag in ("a", "b"):
        outer, inner = recs[f"{tag}/outer"], recs[f"{tag}/inner"]
        # nesting resolved per-thread: never across threads
        assert inner.parent_id == outer.span_id
        assert outer.parent_id is None
        assert inner.tid == outer.tid
    assert recs["a/outer"].tid != recs["b/outer"].tid
    assert recs["a/outer"].thread_name == "Wa"


def test_enable_disable_toggles_capture():
    obs.enable()
    with obs.span("while-on", "t"):
        pass
    obs.disable()
    with obs.span("while-off", "t"):
        pass
    names = [r.name for r in obs.captured()]
    assert names == ["while-on"]  # captured records stay readable


def test_ring_buffer_bounded():
    obs.enable(buffer_size=16)
    for k in range(64):
        with obs.span(f"s{k}", "t"):
            pass
    recs = obs.captured()
    assert len(recs) == 16
    assert recs[0].name == "s48" and recs[-1].name == "s63"  # newest kept


# ---- metrics registry ----

def test_counter_gauge_interning_and_labels():
    reg = obs.registry()
    c1 = reg.counter("x.total", model="m", bucket=8)
    c2 = reg.counter("x.total", bucket=8, model="m")  # order-insensitive
    assert c1 is c2
    c1.add(2)
    c2.add(0.5)
    assert reg.counter("x.total", model="m", bucket=8).value == 2.5
    assert reg.counter("x.total", model="other").value == 0  # distinct
    with pytest.raises(ValueError):
        c1.add(-1)
    g = reg.gauge("x.depth")
    assert g.value is None
    g.set(3)
    g.add(1)
    assert g.value == 4.0
    snap = reg.snapshot()
    assert snap["counters"]["x.total{bucket=8,model=m}"] == 2.5
    assert snap["gauges"]["x.depth"] == 4.0


def test_histogram_percentiles_match_numpy():
    rng = np.random.default_rng(7)
    values = rng.normal(size=500).tolist()
    h = obs.registry().histogram("lat", window=1024)
    for v in values:
        h.observe(v)
    p = h.percentiles(ndigits=None)
    p50, p95, p99 = np.percentile(np.asarray(values), [50, 95, 99])
    assert p["n"] == 500
    assert p["p50"] == pytest.approx(float(p50))
    assert p["p95"] == pytest.approx(float(p95))
    assert p["p99"] == pytest.approx(float(p99))
    assert h.count == 500 and h.sum == pytest.approx(sum(values))


def test_histogram_window_bounds_memory_but_not_count():
    h = obs.registry().histogram("w", window=8)
    for v in range(100):
        h.observe(v)
    assert h.count == 100  # lifetime count exact
    assert h.values() == list(range(92, 100))  # window keeps the newest
    assert h.percentiles()["n"] == 8


def test_empty_histogram_is_snapshot_safe():
    h = obs.registry().histogram("never")
    assert h.percentiles() is None and h.mean() is None
    snap = obs.registry().snapshot()["histograms"]["never"]
    assert snap["count"] == 0 and snap["percentiles"] is None
    json.dumps(snap)


# ---- Chrome-trace export ----

def test_chrome_trace_is_valid_trace_event_json():
    obs.enable()
    with obs.span("parent", "plan", {"rows": 4}):
        with obs.span("child", "plan"):
            pass
    obs.event("mark", "serve", {"model": "m"})
    payload = json.loads(json.dumps(obs.chrome_trace()))  # JSON-safe
    events = payload["traceEvents"]
    complete = [e for e in events if e["ph"] == "X"]
    instants = [e for e in events if e["ph"] == "i"]
    meta = [e for e in events if e["ph"] == "M"]
    assert len(complete) == 2 and len(instants) == 1 and len(meta) >= 1
    for e in complete:
        # the trace_event contract chrome://tracing / Perfetto require
        assert set(e) >= {"name", "cat", "ph", "ts", "dur", "pid", "tid"}
        assert isinstance(e["ts"], float) and e["dur"] >= 0
    by_name = {e["name"]: e for e in complete}
    parent, child = by_name["parent"], by_name["child"]
    # nesting: same lane, child interval inside the parent's
    assert child["tid"] == parent["tid"]
    assert child["ts"] >= parent["ts"]
    assert child["ts"] + child["dur"] <= parent["ts"] + parent["dur"]
    assert child["args"]["parent_id"] == parent["args"]["span_id"]
    assert parent["args"]["rows"] == 4
    assert meta[0]["name"] == "thread_name"


def test_summarize_spans_aggregates_by_name():
    obs.enable()
    for _ in range(3):
        with obs.span("hot", "t"):
            pass
    with obs.span("cold", "t"):
        pass
    from mmlspark_tpu.obs.export import summarize_spans
    rows = {r["name"]: r for r in summarize_spans()}
    assert rows["hot"]["calls"] == 3 and rows["cold"]["calls"] == 1
    assert rows["hot"]["total_ms"] >= rows["hot"]["mean_ms"]


# ---- the acceptance contract: obs counters == the PR 1 seam counts ----

def _registry_crossings() -> dict:
    counters = obs.registry().snapshot()["counters"]
    shapes = obs.registry().series("plan.h2d_shapes")
    return {
        "uploads": counters.get("plan.h2d_uploads", 0),
        "fetches": counters.get("plan.d2h_fetches", 0),
        "upload_bytes": counters.get("plan.h2d_bytes", 0),
        "distinct_shapes": len(shapes),
    }


def parity_pipelines():
    """The tests/test_plan.py parity scenarios, rebuilt here: every fused
    shape the PR 1 suite pins, plus the host-fallback case that must
    count ZERO crossings."""
    return [
        ("crop_flip_unroll",
         [ImageTransformer().crop(2, 3, 16, 12).flip(-1),
          UnrollImage(scale=1.0, offset=0.0)],
         image_table()),
        ("resize_unroll",
         [ImageTransformer().resize(16, 12), UnrollImage()],
         image_table(h=29, w=23)),
        ("three_stage_model_tail_padding",
         [ImageTransformer().flip(0),
          AssembleFeatures(columns_to_featurize=["image"],
                           allow_images=True,
                           features_col="features").fit(
              image_table(n=10, h=12, w=10)),
          JaxModel(model=mlp_bundle(2 + 12 * 10 * 3),
                   input_col="features", output_col="scores",
                   minibatch_size=4, mesh_spec={"dp": 1})],
         image_table(n=10, h=12, w=10)),
        ("chained_models",
         [JaxModel(model=mlp_bundle(6, out_dim=5, seed=1), input_col="x",
                   output_col="h", minibatch_size=4),
          JaxModel(model=mlp_bundle(5, out_dim=3, seed=2), input_col="h",
                   output_col="scores", minibatch_size=4)],
         DataTable({"x": list(np.random.default_rng(3).normal(
             size=(9, 6)).astype(np.float32))})),
        ("ragged_host_fallback",
         [ImageTransformer().flip(1), UnrollImage()],
         DataTable({"image": [
             make_image(f"p{k}",
                        np.random.default_rng(5).integers(
                            0, 255, (10 + k, 8, 3)))
             for k in range(5)]})),
    ]


@pytest.mark.parametrize("name,stages,table",
                         parity_pipelines(),
                         ids=[p[0] for p in parity_pipelines()])
def test_obs_counters_equal_seam_counts_for_parity_pipelines(
        name, stages, table):
    """For every PR 1 parity pipeline the registry's crossing counters
    must EXACTLY equal what the independent seam-patching counter
    observes: crossings, bytes, and the distinct-upload-shape recompile
    surface. (The ragged case pins the zero: a host fallback records no
    phantom crossings.)"""
    obs.enable()
    with plan.count_crossings() as c:
        PipelineModel(stages).transform(table)
    got = _registry_crossings()
    assert got["uploads"] == c.uploads
    assert got["fetches"] == c.fetches
    assert got["upload_bytes"] == c.upload_bytes
    assert got["distinct_shapes"] == len(c.upload_shapes)
    if name == "ragged_host_fallback":
        assert got["uploads"] == 0 and got["upload_bytes"] == 0


def test_obs_compile_counter_counts_segment_builds():
    obs.enable()
    table = image_table(n=6)
    pm = PipelineModel([ImageTransformer().flip(1), UnrollImage()])
    pm.transform(table)
    first = obs.registry().value("plan.segment_compiles")
    assert first == 1
    pm.transform(table)  # cache hit: no new compile
    assert obs.registry().value("plan.segment_compiles") == first
    assert obs.compiled_programs(pm) == 1


# ---- serve burst: one substrate across the PR 4 observables ----

def test_serve_burst_obs_counters_match_pr4_observables():
    """One serve burst: the registry's crossing/shape counters, the
    obs-owned compile-cache hook, and the re-backed ServerStats snapshot
    must all agree with the independently counted values the PR 4 tests
    assert."""
    from mmlspark_tpu.models.zoo import get_model
    from mmlspark_tpu.serve import ModelServer, ServeConfig

    buckets, n_req = (1, 8, 32), 48
    bundle = get_model("ConvNet_CIFAR10", widths=(8, 16), dense_width=32)
    jm = JaxModel(model=bundle, input_col="image", output_col="scores")
    rng = np.random.default_rng(0)
    rows = rng.integers(0, 255, (n_req, 32 * 32 * 3)).astype(np.uint8)

    server = ModelServer(ServeConfig(buckets=buckets, max_queue=n_req,
                                     deadline_ms=None))
    try:
        server.add_model("cnn", jm,
                         example=DataTable({"image": [rows[0]]}))
        obs.enable()  # after warmup: count the burst only
        # the upload counters are always on (boundary tier), so warm-up
        # has counted already: the burst is the growth from here
        warm = _registry_crossings()
        with plan.count_crossings() as c:
            handles = [server.submit("cnn",
                                     DataTable({"image": [rows[i]]}))
                       for i in range(n_req)]
            outs = [h.result(timeout=300) for h in handles]
        snap = server.stats("cnn").snapshot()
        programs = server.compiled_programs("cnn")
        entry = server._entry("cnn")
        obs_programs = obs.compiled_programs(entry.batcher.cache_host)
    finally:
        server.close()

    assert all(len(o) == 1 and "scores" in o for o in outs)
    got = _registry_crossings()
    # crossings + bytes + recompile surface: registry == seam counter
    assert got["uploads"] - warm["uploads"] == c.uploads
    assert got["fetches"] == c.fetches
    assert got["upload_bytes"] - warm["upload_bytes"] == c.upload_bytes
    assert got["distinct_shapes"] == len(c.upload_shapes)
    assert got["distinct_shapes"] <= len(buckets)
    # the compile hook is obs-owned and serve-delegated: same number
    assert programs == obs_programs
    if programs is not None:
        assert programs <= len(buckets)
    # re-backed ServerStats stays value-compatible under real traffic
    assert snap["completed"] == n_req
    assert snap["rows_dispatched"] == n_req
    assert snap["distinct_batch_shapes"] <= len(buckets)
    assert sum(snap["occupancy_by_bucket"].values()) == snap["batches"]
    # serve spans landed on the timeline alongside the plan spans
    cats = {r.cat for r in obs.captured() if isinstance(r, SpanRecord)}
    assert "serve" in cats and "plan" in cats


# ---- train: loader spans + input_stats as a registry view ----

def test_trainer_input_stats_published_as_registry_view():
    from mmlspark_tpu.models.zoo import MLP
    from mmlspark_tpu.train.loop import TrainConfig, Trainer

    obs.enable()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 6)).astype(np.float32)
    y = rng.integers(0, 4, 64).astype(np.int64)
    cfg = TrainConfig(batch_size=16, epochs=1, prefetch_depth=2,
                      log_every=2)
    tr = Trainer(MLP(features=(8,), num_outputs=4), cfg)
    tr.fit_arrays(x, y)

    stats = tr.input_stats
    assert stats is not None and stats["batches"] == 4
    reg = obs.registry()
    # every input_stats key is a gauge in the shared registry with the
    # SAME value — Trainer.input_stats is a view over the substrate
    for key, val in stats.items():
        g = reg.gauge(f"train.input.{key}", loader="fit_arrays")
        assert g.value == val, (key, g.value, val)
    assert reg.value("train.steps") == 4
    names = {r.name for r in obs.captured() if isinstance(r, SpanRecord)}
    assert "train/step" in names
    assert "fit_arrays/commit" in names
    assert "fit_arrays/wait" in names


def test_decode_chunk_span_and_counters(tmp_path):
    cv2 = pytest.importorskip("cv2")
    from mmlspark_tpu.data.readers import read_images

    img = np.zeros((8, 8, 3), np.uint8)
    for k in range(3):
        cv2.imwrite(str(tmp_path / f"im{k}.png"), img)
    obs.enable()
    out = read_images(str(tmp_path))
    assert len(out) == 3
    names = {r.name for r in obs.captured() if isinstance(r, SpanRecord)}
    assert "data/decode_chunk" in names
    assert obs.registry().value("data.images_decoded") == 3


# ---- request-scoped tracing (obs/context.py) ----


def test_mint_is_none_when_disabled_and_unique_when_enabled():
    assert obs.mint() is None  # the disabled path: one flag check
    obs.enable()
    ids = [obs.mint() for _ in range(100)]
    assert len(set(ids)) == 100 and all(isinstance(t, int) for t in ids)


def test_spans_inherit_bound_trace_across_threads():
    from mmlspark_tpu.obs import context
    obs.enable()
    t1 = obs.mint()

    def worker():
        # a DIFFERENT thread binding the same trace: its spans belong
        # to the same request — the batcher's thread-hop case
        with context.bind(t1):
            with obs.span("lane/work", "serve"):
                pass

    with context.bind(t1):
        with obs.span("caller/work", "serve"):
            pass
    assert context.current() is None  # binding restored on exit
    th = threading.Thread(target=worker)
    th.start()
    th.join()
    recs = [r for r in obs.captured() if isinstance(r, SpanRecord)]
    assert {r.name for r in recs} == {"caller/work", "lane/work"}
    assert all(r.trace == t1 for r in recs)
    assert len({r.tid for r in recs}) == 2  # genuinely two threads


def test_bind_nests_and_restores_previous_trace():
    from mmlspark_tpu.obs import context
    obs.enable()
    t1, t2 = obs.mint(), obs.mint()
    with context.bind(t1):
        assert context.current() == t1
        with context.bind(t2):
            assert context.current() == t2
        assert context.current() == t1
        with context.bind(None):  # explicit clear (worker reuse)
            assert context.current() is None
        assert context.current() == t1
    assert context.current() is None


def _journey(t, *, admit=1, complete=1):
    """Record one synthetic request journey for trace id ``t``."""
    from mmlspark_tpu.obs import context
    for _ in range(admit):
        with context.bind(t):
            with obs.span("serve/admit", "serve"):
                pass
    for name in ("serve/pack", "serve/dispatch", "serve/drain"):
        with obs.span(name, "serve", links=(t,)):
            pass
    for _ in range(complete):
        with context.bind(t):
            with obs.span("serve/complete", "serve"):
                pass


def test_request_traces_groups_by_trace_and_links():
    obs.enable()
    t1, t2 = obs.mint(), obs.mint()
    # two requests coalesced into ONE batch: shared pack/dispatch/drain
    from mmlspark_tpu.obs import context
    for t in (t1, t2):
        with context.bind(t):
            with obs.span("serve/admit", "serve"):
                pass
    for name in ("serve/pack", "serve/dispatch", "serve/drain"):
        with obs.span(name, "serve", links=(t1, t2)):
            pass
    for t in (t1, t2):
        with context.bind(t):
            with obs.span("serve/complete", "serve"):
                pass
    traces = obs.request_traces()
    assert set(traces) == {t1, t2}
    for t in (t1, t2):
        assert obs.check_journey(traces[t]) is None
        names = [s.name for s in traces[t]]
        assert names[0] == "serve/admit" and names[-1] == "serve/complete"
        # the SHARED batch spans appear in both traces
        assert "serve/pack" in names and "serve/drain" in names


def test_check_journey_flags_missing_and_duplicated_spans():
    obs.enable()
    t = obs.mint()
    from mmlspark_tpu.obs import context
    with context.bind(t):
        with obs.span("serve/admit", "serve"):
            pass
    # half a journey: no batch spans, no completion
    traces = obs.request_traces()
    why = obs.check_journey(traces[t])
    assert why is not None and "serve/pack" in why
    # a duplicated endpoint is flagged too
    t2 = obs.mint()
    _journey(t2, admit=2)
    why2 = obs.check_journey(obs.request_traces()[t2])
    assert why2 is not None and "serve/admit" in why2


def test_chrome_trace_emits_flow_events_binding_the_journey():
    obs.enable()
    t = obs.mint()
    _journey(t)
    payload = json.loads(json.dumps(obs.chrome_trace()))
    flows = [e for e in payload["traceEvents"]
             if e.get("ph") in ("s", "t", "f")]
    mine = sorted((e for e in flows if e["id"] == t),
                  key=lambda e: e["ts"])
    # one flow: a start, three steps (pack/dispatch/drain), a finish
    assert [e["ph"] for e in mine] == ["s", "t", "t", "t", "f"]
    assert all(e.get("bp") == "e" for e in mine)
    # the complete events carry the trace/links in args for debugging
    admits = [e for e in payload["traceEvents"]
              if e.get("ph") == "X" and e["name"] == "serve/admit"]
    assert admits and admits[0]["args"]["trace"] == t
    packs = [e for e in payload["traceEvents"]
             if e.get("ph") == "X" and e["name"] == "serve/pack"]
    assert packs and packs[0]["args"]["links"] == [t]


def test_single_touch_trace_emits_no_flow():
    obs.enable()
    t = obs.mint()
    from mmlspark_tpu.obs import context
    with context.bind(t):
        with obs.span("serve/admit", "serve"):
            pass
    flows = [e for e in obs.chrome_trace()["traceEvents"]
             if e.get("ph") in ("s", "t", "f")]
    assert flows == []  # an arrow needs two ends


# ---- trace retention (the request_traces eviction policy) ----


def test_sustained_trace_burst_cannot_grow_memory_unboundedly():
    """Regression (PR 9 satellite): completed traces used to be retained
    for grouping until someone called clear() — a server left tracing
    under sustained traffic grew request_traces() without bound. The
    retention policy drops the OLDEST traces past ``max_traces``,
    evicts their spans from the ring, and counts the drops."""
    from mmlspark_tpu.obs import runtime as rt
    obs.enable(max_traces=64)
    n_burst = 2048
    for _ in range(n_burst):
        _journey(obs.mint())
    live = rt.live_traces()
    assert len(live) <= 64, (
        f"{len(live)} live traces retained against a bound of 64")
    traces = obs.request_traces()
    assert len(traces) <= 64
    # the newest traces survive, the oldest are gone (drop-OLDEST)
    assert max(traces) == max(live)
    assert min(traces) > n_burst - 128
    # the dropped traces' spans actually left the ring (memory, not
    # just the grouping view)
    for r in rt.spans():
        tr = getattr(r, "trace", None)
        links = getattr(r, "links", None) or ()
        if tr is not None or links:
            assert (tr in live) or any(t in live for t in links)
    dropped = obs.registry().value("obs.traces_dropped")
    assert dropped is not None and dropped >= n_burst - 64
    assert rt.dropped_trace_count() == dropped


def test_trace_eviction_spares_non_request_records():
    from mmlspark_tpu.obs import runtime as rt
    obs.enable(max_traces=8)
    with obs.span("train/step", "train"):  # no trace id: never evicted
        pass
    for _ in range(64):
        _journey(obs.mint())
    names = [getattr(r, "name", "") for r in rt.spans()]
    assert "train/step" in names, (
        "trace eviction evicted a span that carries no trace id")


def test_evicted_trace_is_not_resurrected_by_late_spans():
    """Regression: a trace dropped while its request was still in
    flight was re-registered as the NEWEST trace when its tail span
    completed — request_traces() then reported a broken journey for a
    partial, tail-only trace (and a second eviction double-counted the
    drop)."""
    from mmlspark_tpu.obs import context, runtime as rt
    obs.enable(max_traces=8)
    victim = obs.mint()
    with context.bind(victim):
        with obs.span("serve/admit", "serve"):
            pass
    # push the victim out of retention while it is "in flight"
    for _ in range(64):
        _journey(obs.mint())
    assert victim not in rt.live_traces()
    dropped_before = rt.dropped_trace_count()
    # the late tail span completes AFTER eviction
    with context.bind(victim):
        with obs.span("serve/complete", "serve"):
            pass
    assert victim not in rt.live_traces(), "dropped trace resurrected"
    assert victim not in obs.request_traces(), (
        "tail-only partial trace grouped after eviction")
    # and the drop is never double-counted by later evictions
    for _ in range(64):
        _journey(obs.mint())
    drops = rt.dropped_trace_count() - dropped_before
    assert drops == 64, f"{drops} drops for 64 new traces"


def test_enable_without_max_traces_restores_default_bound():
    """Regression: ``enable(max_traces=4)`` used to leave the tiny bound
    sticky for every later ``enable()`` in the process — a 200-request
    burst after a re-bounded enable retained 4 traces. Omitting the
    kwarg restores the default, same as ``buffer_size`` does."""
    from mmlspark_tpu.obs import device as obs_device
    from mmlspark_tpu.obs import runtime as rt
    obs.enable(max_traces=4, device=True)
    assert rt._max_traces == 4 and obs_device.enabled()
    obs.enable()
    assert rt._max_traces == rt.DEFAULT_MAX_TRACES
    # the device pillar follows the same rule: omitted → back to the
    # env baseline (off here)
    assert not obs_device.enabled()
    for _ in range(32):
        _journey(obs.mint())
    assert len(obs.request_traces()) == 32
    # with MMLSPARK_TPU_OBS_DEVICE=1 the baseline is ON: a library's
    # plain enable() must not defeat the no-code-changes env path
    from mmlspark_tpu.core import config
    config.set("obs_device", True)
    try:
        obs.enable()
        assert obs_device.enabled(), (
            "plain enable() defeated the env device baseline")
        obs.enable(device=False)  # explicit off still wins
        assert not obs_device.enabled()
    finally:
        config.set("obs_device", False)


def test_request_traces_explicit_records_bypass_retention():
    """A caller-supplied record list is the caller's retention problem —
    the filter applies only to the runtime ring's view."""
    obs.enable(max_traces=4)
    ids = []
    for _ in range(16):
        t = obs.mint()
        ids.append(t)
        _journey(t)
    kept = obs.captured()
    # grouping the ring honors the bound…
    assert len(obs.request_traces()) <= 4
    # …but an explicit list groups everything it holds
    explicit = obs.request_traces(kept)
    assert set(explicit) <= set(ids)
    assert len(explicit) >= len(obs.request_traces())


# ---- SLO engine (obs/slo.py) ----


def _slo_stats(model="m"):
    from mmlspark_tpu.serve.stats import ServerStats
    return ServerStats(window=64, model=model)


def test_slo_tracker_burn_rates_from_counter_deltas():
    from mmlspark_tpu.obs.slo import SLOSpec, SLOTracker
    spec = SLOSpec(objective=0.9, window_s=10.0, long_window_s=40.0,
                   min_requests=5)
    stats = _slo_stats()
    tracker = SLOTracker(spec, stats, queued_fn=lambda: 3)
    s0 = tracker.sample(now=0.0)
    assert s0["burn_rate_short"] is None  # one sample: no delta yet
    assert s0["queue_depth"] == 3
    # 10s later: 20 terminal requests, 4 failed → 20% errors on a 10%
    # budget → burn 2.0
    for _ in range(16):
        stats.record_admitted()
        stats.record_done(e2e_ms=5.0, queue_ms=1.0)
    for _ in range(4):
        stats.record_admitted()
        stats.record_failed()
    s1 = tracker.sample(now=10.0)
    assert s1["burn_rate_short"] == pytest.approx(2.0)
    assert s1["window_short"]["terminal"] == 20
    assert s1["window_short"]["errors"] == 4
    # lifetime error rate (20%) is 2x the whole budget: remaining
    # clamps at zero rather than going negative
    assert s1["budget_remaining"] == 0.0
    # quiet window: deltas vs the 10s-old sample go to zero traffic
    s2 = tracker.sample(now=20.0)
    assert s2["burn_rate_short"] is None  # < min_requests in window
    assert s2["window_short"]["terminal"] == 0


def test_slo_tracker_ignores_thin_windows():
    from mmlspark_tpu.obs.slo import SLOSpec, SLOTracker
    spec = SLOSpec(objective=0.99, window_s=10.0, long_window_s=20.0,
                   min_requests=10)
    stats = _slo_stats()
    tracker = SLOTracker(spec, stats)
    tracker.sample(now=0.0)
    stats.record_admitted()
    stats.record_failed()  # 100% errors, but only ONE request
    s = tracker.sample(now=10.0)
    assert s["burn_rate_short"] is None  # no verdict below min_requests
    assert s["window_short"]["errors"] == 1


def test_slo_tracker_long_window_survives_frequent_polling():
    """A dashboard polling /slo + /healthz at high frequency must not
    evict the long window's base sample — the ring is bounded by time
    (with sub-resolution appends coalesced), not a fixed maxlen that
    would silently collapse burn_rate_long onto a recent window."""
    from mmlspark_tpu.obs.slo import SLOSpec, SLOTracker
    spec = SLOSpec(objective=0.9, window_s=10.0, long_window_s=40.0,
                   min_requests=5)
    stats = _slo_stats()
    tracker = SLOTracker(spec, stats)
    tracker.sample(now=0.0)
    # the incident happens early: 20 terminal requests, 4 failed
    for _ in range(16):
        stats.record_admitted()
        stats.record_done(e2e_ms=5.0, queue_ms=1.0)
    for _ in range(4):
        stats.record_admitted()
        stats.record_failed()
    # then 2500 polls over 5 s — far more than any fixed sample cap
    for i in range(2500):
        tracker.sample(now=5.0 + i * 0.002)
    s = tracker.sample(now=41.0)
    # the 40 s base is still the t=0 sample: the incident stays visible
    assert s["window_long"]["terminal"] == 20
    assert s["window_long"]["errors"] == 4
    assert s["burn_rate_long"] == pytest.approx(2.0)
    # and coalescing kept the ring bounded despite the poll rate
    assert len(tracker._samples) < 8200


def test_slo_tracker_sub_resolution_polling_from_cold_start():
    """An LB probing every 2 ms from process start — faster than the
    ring resolution (long_window_s/4096 ≈ 9.8 ms here) with no slower
    poll ever banking a base sample — must still converge to a burn
    verdict. Coalescing replaces the tail slot's reads but keeps its
    original timestamp, so the slot ages past the resolution step and
    base samples accumulate; rewriting the timestamp made the tail a
    sliding target that kept the engine verdict-less forever."""
    from mmlspark_tpu.obs.slo import SLOSpec, SLOTracker
    spec = SLOSpec(objective=0.9, window_s=10.0, long_window_s=40.0,
                   min_requests=5)
    stats = _slo_stats()
    tracker = SLOTracker(spec, stats)
    for i in range(1000):          # t = 0 .. 2 s, quiet
        tracker.sample(now=i * 0.002)
    for _ in range(16):
        stats.record_admitted()
        stats.record_done(e2e_ms=5.0, queue_ms=1.0)
    for _ in range(4):
        stats.record_admitted()
        stats.record_failed()
    s = None
    for i in range(1000, 5501):    # keep probing through t = 11 s
        s = tracker.sample(now=i * 0.002)
    # the 10 s short-window base (a slot near t = 1 s) predates the
    # incident: the burn is visible instead of None-forever
    assert s["window_short"]["terminal"] == 20
    assert s["window_short"]["errors"] == 4
    assert s["burn_rate_short"] == pytest.approx(2.0)


def test_slo_latency_objective_and_derived_gauges():
    from mmlspark_tpu.obs.slo import SLOSpec, SLOTracker
    spec = SLOSpec(objective=0.999, latency_ms=50.0,
                   latency_quantile="p99")
    stats = _slo_stats()
    stats.record_batch(bucket=8, occupancy=6, device_ms=4.0,
                       replica=0)
    stats.record_batch(bucket=8, occupancy=2, device_ms=4.0,
                       replica=0)
    stats.record_batch(bucket=8, occupancy=8, device_ms=4.0,
                       replica=1)
    for ms in (10.0, 20.0, 200.0):
        stats.record_admitted()
        stats.record_done(e2e_ms=ms, queue_ms=1.0)
    tracker = SLOTracker(spec, stats, queued_fn=lambda: 7)
    s = tracker.sample(now=0.0)
    assert s["latency_ok"] is False and s["latency_ms"] > 50.0
    # derived gauges landed in the model's own registry
    reg = stats.registry
    assert reg.gauge("serve.queue_depth", model="m").value == 7.0
    assert reg.gauge("serve.occupancy_mean_window",
                     model="m").value == pytest.approx(16 / 3, abs=1e-3)
    # replica skew from the replica_batches counters: 2 vs 1 → 0.5
    assert reg.gauge("serve.replica_skew", model="m").value \
        == pytest.approx(0.5)
    assert s["replica_skew"] == pytest.approx(0.5)


def test_slo_spec_validation_and_parse():
    from mmlspark_tpu.obs.slo import SLOSpec
    with pytest.raises(ValueError):
        SLOSpec(objective=1.0)
    with pytest.raises(ValueError):
        SLOSpec(latency_quantile="p90")
    with pytest.raises(ValueError):
        SLOSpec(window_s=60.0, long_window_s=30.0)
    with pytest.raises(ValueError):
        SLOSpec(min_requests=0)  # would divide by a zero-traffic window
    with pytest.raises(ValueError):
        SLOSpec(fast_burn=0.0)
    with pytest.raises(ValueError):
        SLOSpec(slow_burn=-1.0)
    assert SLOSpec.parse(None).objective == 0.999
    parsed = SLOSpec.parse({"objective": 0.95, "latency_ms": 100.0})
    assert parsed.objective == 0.95 and parsed.budget == \
        pytest.approx(0.05)
    assert SLOSpec.parse(parsed) is parsed
    with pytest.raises(TypeError):
        SLOSpec.parse("p99<100ms")


def test_slow_step_detector_flags_outliers_and_rebaselines():
    from mmlspark_tpu.obs.slo import SlowStepDetector
    obs.enable()
    det = SlowStepDetector(loop="t", factor=3.0, min_samples=4,
                           window=8)
    assert not any(det.observe(10.0) for _ in range(4))  # baseline
    assert det.observe(100.0) is True  # 10x the median
    assert det.observe(12.0) is False
    assert obs.registry().value("train.slow_steps", loop="t") == 1
    events = [r for r in obs.captured()
              if getattr(r, "name", "") == "train/slow_step"]
    assert len(events) == 1 and events[0].labels["step_ms"] == 100.0
    # regime change: consistently slower steps re-baseline via the
    # window median instead of flagging forever
    for _ in range(8):
        det.observe(100.0)
    assert det.observe(110.0) is False


def test_slow_step_detector_baseline_is_per_instance():
    """The train.step_ms{loop=...} histogram is interned process-wide,
    but a fresh detector (a new fit) must baseline against ITS OWN
    steps — not the previous fit's window, which would flag every step
    of a legitimately slower run."""
    from mmlspark_tpu.obs.slo import SlowStepDetector
    obs.enable()
    fast = SlowStepDetector(loop="t2", factor=3.0, min_samples=4,
                            window=8)
    for _ in range(8):
        fast.observe(0.5)
    slow = SlowStepDetector(loop="t2", factor=3.0, min_samples=4,
                            window=8)
    # 5.0 ms steps are 10x the previous fit's median, but this fit's
    # own baseline is 5.0 — nothing is slow
    assert not any(slow.observe(5.0) for _ in range(8))
    assert obs.registry().value("train.slow_steps", loop="t2") == 0


def test_trainer_publishes_step_histogram_and_slow_counter():
    from mmlspark_tpu.models.zoo import MLP
    from mmlspark_tpu.train.loop import TrainConfig, Trainer

    obs.enable()
    rng = np.random.default_rng(0)
    x = rng.normal(size=(64, 6)).astype(np.float32)
    y = rng.integers(0, 4, 64).astype(np.int64)
    tr = Trainer(MLP(features=(8,), num_outputs=4),
                 TrainConfig(batch_size=16, epochs=1, prefetch_depth=2))
    tr.fit_arrays(x, y)
    h = obs.registry().histogram("train.step_ms", loop="fit_arrays")
    assert h.count == 4  # one observation per step
    assert obs.registry().value("train.slow_steps",
                                loop="fit_arrays") is not None


# ---- health state machine (obs/health.py) ----


def _status(burn_short=None, burn_long=None, latency_ok=None,
            admitted=0, rejected=0, terminal=0):
    return {
        "burn_rate_short": burn_short,
        "burn_rate_long": burn_long,
        "latency_ok": latency_ok,
        "latency_ms": 10.0,
        "slo": {"latency_ms": 5.0, "latency_quantile": "p99"},
        "window_short": {"admitted": admitted, "rejected": rejected,
                         "terminal": terminal},
    }


def test_health_classification_levels():
    from mmlspark_tpu.obs.health import (
        DEGRADED, OK, UNHEALTHY, HealthPolicy, classify,
    )
    pol = HealthPolicy(fast_burn=10.0, slow_burn=2.0, min_events=5)
    assert classify(_status(), pol) == (OK, "")
    lvl, why = classify(_status(burn_short=12.0), pol)
    assert lvl == UNHEALTHY and "burn" in why
    lvl, why = classify(_status(burn_long=3.0), pol)
    assert lvl == DEGRADED and "long-window" in why
    lvl, why = classify(_status(latency_ok=False, terminal=10), pol)
    assert lvl == DEGRADED and "latency" in why
    # a frozen e2e reservoir (violating percentiles, no fresh window
    # traffic) is NOT a live violation — otherwise one cold-compile
    # spike would hold DEGRADED forever after traffic stops
    assert classify(_status(latency_ok=False), pol) == (OK, "")
    # admission bouncing most arrivals is unhealthy even with no
    # completed-request errors (Overloaded is backpressure)
    lvl, why = classify(_status(admitted=4, rejected=8), pol)
    assert lvl == UNHEALTHY and "rejecting" in why
    # ... but not below the event floor
    assert classify(_status(admitted=1, rejected=2), pol) == (OK, "")


def test_health_monitor_hysteresis():
    from mmlspark_tpu.obs.health import (
        DEGRADED, OK, UNHEALTHY, HealthMonitor, HealthPolicy,
    )
    mon = HealthMonitor(HealthPolicy(fast_burn=10.0, slow_burn=2.0,
                                     recover_after=3))
    assert mon.update(_status()) == OK
    # worsening applies immediately
    assert mon.update(_status(burn_short=20.0)) == UNHEALTHY
    assert mon.reason
    # recovery needs recover_after consecutive better samples
    assert mon.update(_status()) == UNHEALTHY
    assert mon.update(_status()) == UNHEALTHY
    assert mon.update(_status()) == OK
    # a relapse mid-streak resets it
    assert mon.update(_status(burn_long=5.0)) == DEGRADED
    assert mon.update(_status()) == DEGRADED
    assert mon.update(_status(burn_long=5.0)) == DEGRADED
    assert mon.update(_status()) == DEGRADED
    assert mon.update(_status()) == DEGRADED
    assert mon.update(_status()) == OK


def test_health_recovers_after_latency_spike_traffic_stops():
    """A latency violation backed by window traffic degrades; once
    traffic stops the reservoir stays frozen at the bad percentiles,
    but the verdict expires with the window and hysteresis recovers."""
    from mmlspark_tpu.obs.health import (
        DEGRADED, OK, HealthMonitor, HealthPolicy,
    )
    mon = HealthMonitor(HealthPolicy(min_events=5, recover_after=3))
    assert mon.update(_status(latency_ok=False, terminal=10)) == DEGRADED
    # traffic stops: percentiles still violating, window empty
    assert mon.update(_status(latency_ok=False)) == DEGRADED
    assert mon.update(_status(latency_ok=False)) == DEGRADED
    assert mon.update(_status(latency_ok=False)) == OK


def test_worst_of_states():
    from mmlspark_tpu.obs.health import worst
    assert worst([]) == "ok"
    assert worst(["ok", "degraded", "ok"]) == "degraded"
    assert worst(["degraded", "unhealthy"]) == "unhealthy"


# ---- Prometheus text exposition ----


def test_prometheus_text_exposition_format():
    from mmlspark_tpu.obs.export import prometheus_text
    reg = obs.registry()
    reg.counter("serve.admitted", model="m").add(3)
    reg.gauge("serve.queue_depth", model="m").set(2)
    reg.gauge("never.set")  # unset gauge: skipped (no null in prom)
    h = reg.histogram("serve.e2e_ms", window=16, model="m")
    for v in (1.0, 2.0, 3.0, 4.0):
        h.observe(v)
    text = prometheus_text()
    lines = text.splitlines()
    assert "# TYPE serve_admitted counter" in lines
    assert 'serve_admitted{model="m"} 3' in lines
    assert "# TYPE serve_queue_depth gauge" in lines
    assert 'serve_queue_depth{model="m"} 2' in lines
    assert "# TYPE serve_e2e_ms summary" in lines
    assert 'serve_e2e_ms{model="m",quantile="0.5"} 2.5' in lines
    assert 'serve_e2e_ms_count{model="m"} 4' in lines
    assert 'serve_e2e_ms_sum{model="m"} 10' in lines
    assert not any("never_set" in ln for ln in lines)
    # names are sanitized to the prom grammar; output ends with newline
    assert all(" " in ln or ln.startswith("#") for ln in lines)
    assert text.endswith("\n")


def test_prometheus_text_survives_non_finite_values():
    """One NaN/Inf series must not 500 the whole scrape — the registry
    is the shared substrate and any client can record a bad ratio.
    Non-finite samples render as the Prometheus literals."""
    from mmlspark_tpu.obs.export import prometheus_text
    reg = obs.registry()
    reg.gauge("bad.ratio", model="m").set(float("nan"))
    reg.gauge("bad.pos", model="m").set(float("inf"))
    reg.gauge("bad.neg", model="m").set(float("-inf"))
    reg.counter("still.fine").add(2)
    lines = prometheus_text().splitlines()
    assert 'bad_ratio{model="m"} NaN' in lines
    assert 'bad_pos{model="m"} +Inf' in lines
    assert 'bad_neg{model="m"} -Inf' in lines
    assert "still_fine 2" in lines


def test_prometheus_text_merges_registries_and_escapes_labels():
    from mmlspark_tpu.obs.export import prometheus_text
    from mmlspark_tpu.obs.metrics import MetricsRegistry
    r1, r2 = MetricsRegistry(), MetricsRegistry()
    r1.counter("serve.admitted", model="a").add(1)
    r2.counter("serve.admitted", model='b"\\q').add(2)
    text = prometheus_text([r1, r2])
    # ONE TYPE header for the shared name, both series present
    assert text.count("# TYPE serve_admitted counter") == 1
    assert 'serve_admitted{model="a"} 1' in text
    assert 'serve_admitted{model="b\\"\\\\q"} 2' in text


def test_prometheus_help_lines_per_family():
    """# HELP rides next to every # TYPE header: curated text for the
    known metric families, the generic fallback (naming the original
    dotted spelling) for the rest — and ONE pair per name across
    merged registries (the fleet-merged path hands several per-host
    registries to one exposition)."""
    from mmlspark_tpu.obs.export import prometheus_text
    from mmlspark_tpu.obs.metrics import MetricsRegistry
    r1, r2 = MetricsRegistry(), MetricsRegistry()
    r1.gauge("serve.queue_depth", model="a").set(2)
    r2.gauge("serve.queue_depth", model="b").set(3)
    r1.counter("totally.custom_metric").add(1)
    lines = prometheus_text([r1, r2]).splitlines()
    # curated help, once, immediately before its TYPE header
    assert lines.count("# HELP serve_queue_depth Live admission-queue "
                       "depth (the replica autoscaling signal).") == 1
    i = lines.index("# TYPE serve_queue_depth gauge")
    assert lines[i - 1].startswith("# HELP serve_queue_depth ")
    # generic fallback keeps the original dotted name greppable
    fallback = [ln for ln in lines
                if ln.startswith("# HELP totally_custom_metric ")]
    assert len(fallback) == 1
    assert "totally.custom_metric" in fallback[0]
    # every TYPE header has a HELP partner
    types = [ln.split()[2] for ln in lines if ln.startswith("# TYPE")]
    helps = [ln.split()[2] for ln in lines if ln.startswith("# HELP")]
    assert types == helps


def test_prometheus_text_byte_stable():
    """The non-fleet path is byte-stable: two expositions of the same
    registry state are identical bytes (scrape diffing, content
    hashing, and the docs' determinism claim all rely on it)."""
    from mmlspark_tpu.obs.export import prometheus_text
    reg = obs.registry()
    reg.counter("serve.admitted", model="m").add(3)
    reg.gauge("serve.queue_depth", model="m").set(2)
    h = reg.histogram("serve.e2e_ms", window=16, model="m")
    for v in (1.0, 2.0, 3.0):
        h.observe(v)
    first = prometheus_text()
    second = prometheus_text()
    assert first == second
    assert first.encode("utf-8") == second.encode("utf-8")
