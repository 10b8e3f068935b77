"""Device-level observability: the flight recorder (obs/flight.py), the
device-attribution pillar (obs/device.py), and the train anomaly plane
(obs/anomaly.py).

The contracts under test:

* a crash, a hang, or an explicit ``on_crash`` each produce ONE
  self-contained post-mortem dump (recent ring, per-thread stacks,
  registry snapshot, heartbeat table, fingerprint) — bounded by the dump
  budget, never repeated for the same stall, never fired for idle seams;
* the registry's interning and the span ring survive a ≥8-thread hammer
  with no lost counter updates, no duplicate interned series, and the
  ring inside its bound;
* the non-finite sentinel fires EXACTLY once per offending step, in both
  ``fit_arrays`` and ``fit_stream``, and the typed raise carries the
  step;
* the straggler detector names the artificially-delayed host from the
  gathered per-host step-time vector;
* device attribution populates ``plan.segment.*`` cost/memory gauges per
  fused segment, and ``host_phase_split`` decomposes captured plan spans
  into host phases that sum to the wall.
"""

import glob
import json
import math
import os
import sys
import threading
import time

import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))

from test_plan import mlp_bundle  # noqa: E402

from mmlspark_tpu import obs
from mmlspark_tpu.models.jax_model import JaxModel
from mmlspark_tpu.models.zoo import MLP
from mmlspark_tpu.obs import device as obs_device
from mmlspark_tpu.obs import flight
from mmlspark_tpu.obs import runtime as obs_rt
from mmlspark_tpu.obs.anomaly import (
    NonFiniteLossError, NonFiniteSentinel, StragglerDetector,
)
from mmlspark_tpu.data.table import DataTable
from mmlspark_tpu.train import TrainConfig, Trainer


@pytest.fixture(autouse=True)
def flight_isolated():
    """Tracer off, flight recorder off, registry/ring/memos clean on both
    sides of every test — the obs flag-isolation contract extended to
    the new pillars."""
    flight.disable()
    obs.disable()
    obs.clear()
    obs.registry().reset()
    obs_device.reset()
    yield
    flight.disable()
    obs.disable()
    obs.clear()
    obs.registry().reset()
    obs_device.reset()


# ---- flight recorder ----


def test_crash_dump_is_self_contained(tmp_path):
    rec = flight.enable(str(tmp_path))
    assert flight.enabled() and obs.enabled()  # the ring must be live
    with obs.span("train/step", "train"):
        pass
    obs.registry().counter("train.steps").add(3)
    try:
        raise RuntimeError("induced")
    except RuntimeError as e:
        path = flight.on_crash(e, context="test")
    assert path is not None and os.path.exists(path)
    dump = json.loads(open(path).read())
    assert dump["reason"] == "crash"
    assert dump["exception"]["type"] == "RuntimeError"
    assert dump["extra"] == {"context": "test"}
    assert any(r["name"] == "train/step" for r in dump["ring"])
    assert dump["registry"]["counters"]["train.steps"] == 3
    # every live thread's stack is present, including this one's
    names = {t["name"] for t in dump["threads"].values()}
    assert "MainThread" in names and flight.THREAD_NAME in names
    assert all(t["stack"] for t in dump["threads"].values())
    # fingerprint makes the dump interpretable off-box
    assert dump["fingerprint"]["python"]
    assert "mesh" in dump["fingerprint"]  # jax is imported in the suite
    assert rec is flight.recorder()


def test_hang_dump_fires_once_per_stall_and_never_for_idle(tmp_path):
    rec = flight.enable(str(tmp_path), hang_threshold_s=0.15, poll_s=0.03)
    rec.arm("busy/lane")
    rec.arm("idle/lane")
    rec.disarm("idle/lane")  # idle seams are never hangs
    time.sleep(0.6)  # several polls past the threshold
    dumps = glob.glob(os.path.join(str(tmp_path), "flight_hang_*.json"))
    assert len(dumps) == 1, (
        "one stall must produce exactly one dump (stalled flag), and an "
        f"idle heartbeat none — got {len(dumps)}")
    dump = json.loads(open(dumps[0]).read())
    assert dump["extra"]["heartbeat"] == "busy/lane"
    assert dump["extra"]["stalled_for_s"] >= 0.15
    assert dump["heartbeats"]["busy/lane"]["busy"] is True
    assert dump["heartbeats"]["idle/lane"]["busy"] is False
    # a beat resets the stall; a new stall dumps again
    rec.beat("busy/lane")
    time.sleep(0.4)
    dumps = glob.glob(os.path.join(str(tmp_path), "flight_hang_*.json"))
    assert len(dumps) == 2


def test_dump_budget_bounds_a_crash_loop(tmp_path):
    rec = flight.enable(str(tmp_path), max_dumps=2)
    assert rec.dump("crash") is not None
    assert rec.dump("crash") is not None
    assert rec.dump("crash") is None  # budget exhausted, disk protected
    assert len(glob.glob(os.path.join(str(tmp_path), "*.json"))) == 2


def test_thread_excepthook_dumps_and_chains(tmp_path):
    chained = []
    prev = threading.excepthook
    threading.excepthook = lambda args: chained.append(args.exc_type)
    try:
        flight.enable(str(tmp_path))  # chains to the capture hook above

        def boom():
            raise ValueError("thread death")

        t = threading.Thread(target=boom, name="DoomedWorker")
        t.start()
        t.join()
        dumps = glob.glob(os.path.join(str(tmp_path),
                                       "flight_crash_*.json"))
        assert len(dumps) == 1, "an unhandled thread exception must dump"
        dump = json.loads(open(dumps[0]).read())
        assert dump["exception"]["type"] == "ValueError"
        assert dump["extra"]["thread"] == "DoomedWorker"
        assert chained == [ValueError], (
            "the previous threading.excepthook must run after the dump")
        flight.disable()
        assert threading.excepthook is not prev  # ours, restored by
        #                                          uninstall, not pytest's
    finally:
        flight.disable()
        threading.excepthook = prev


def test_enable_is_idempotent_and_disable_restores_hooks(tmp_path):
    prev_except = sys.excepthook
    prev_thread = threading.excepthook
    rec = flight.enable(str(tmp_path))
    assert sys.excepthook is not prev_except
    assert flight.enable(str(tmp_path)) is rec  # same dir → same recorder
    flight.disable()
    # same dir + IDENTICAL kwargs is idempotent too: an "ensure on"
    # call per work cycle must not rebuild the recorder (that would
    # reset the dump budget and wipe heartbeats/crash-dedup state)
    rec2 = flight.enable(str(tmp_path), hang_threshold_s=30.0)
    rec2._dumps = 3  # pretend a crash loop already spent budget
    assert flight.enable(str(tmp_path), hang_threshold_s=30.0) is rec2
    assert rec2._dumps == 3
    # changed kwargs DO rebuild
    rec3 = flight.enable(str(tmp_path), hang_threshold_s=60.0)
    assert rec3 is not rec2 and rec3._dumps == 0
    flight.disable()
    assert sys.excepthook is prev_except
    assert threading.excepthook is prev_thread
    assert flight.recorder() is None
    # the watchdog thread is gone
    assert not any(t.name == flight.THREAD_NAME
                   for t in threading.enumerate())


def test_interning_and_ring_survive_concurrent_hammer(tmp_path):
    """≥8 threads hammering metric interning, flight heartbeats, and
    ring writes concurrently: no lost counter updates, no duplicate
    interned series, the ring inside its bound."""
    n_threads, iters = 8, 400
    obs.enable(buffer_size=512)
    rec = flight.enable(str(tmp_path), hang_threshold_s=60.0)
    reg = obs.registry()
    errors: list = []
    start = threading.Barrier(n_threads)

    def hammer(k: int):
        try:
            start.wait(timeout=10)
            for i in range(iters):
                # same (name, labels) from every thread — interning must
                # hand back ONE series
                reg.counter("hammer.total", lane="shared").add()
                reg.histogram("hammer.ms", lane="shared").observe(float(i))
                rec.beat(f"hammer/{k}")
                with obs.span("hammer/span", "test", {"k": k}):
                    pass
        except Exception as e:  # pragma: no cover - failure surface
            errors.append(e)

    threads = [threading.Thread(target=hammer, args=(k,))
               for k in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors
    assert not any(t.is_alive() for t in threads)
    # no lost updates
    assert reg.value("hammer.total", lane="shared") == n_threads * iters
    series = reg.series("hammer.total")
    assert len(series) == 1, (
        f"{len(series)} interned series for one (name, labels) — "
        "concurrent interning duplicated the counter")
    assert series[0].value == n_threads * iters
    hist = reg.series("hammer.ms")
    assert len(hist) == 1 and hist[0].count == n_threads * iters
    # ring bounded; every heartbeat registered and busy
    assert obs_rt.captured_count() <= 512
    beats = rec.heartbeats()
    assert {f"hammer/{k}" for k in range(n_threads)} <= set(beats)
    assert all(beats[f"hammer/{k}"]["busy"] for k in range(n_threads))


# ---- non-finite sentinel ----


def test_sentinel_unit_fires_once_per_step_and_validates_mode():
    with pytest.raises(ValueError, match="nonfinite_loss"):
        NonFiniteSentinel("x", mode="explode")
    obs.enable()
    s = NonFiniteSentinel("unit", mode="event")
    assert s.check(1, 1.5) == 1.5
    s.check(2, float("nan"))
    s.check(2, float("nan"))  # same step consulted twice → one event
    s.check(3, float("inf"))
    reg = obs.registry()
    assert reg.value("train.nonfinite_losses", loop="unit") == 2
    events = [r for r in obs.captured()
              if getattr(r, "name", "") == "train/nonfinite"]
    assert len(events) == 2
    assert events[0].labels["step"] == 2
    # off mode: no counting, no raise
    off = NonFiniteSentinel("off", mode="off")
    assert math.isnan(off.check(1, float("nan")))
    assert reg.value("train.nonfinite_losses", loop="off") is None


def _nan_xy(n=32):
    rng = np.random.default_rng(0)
    x = rng.normal(size=(n, 6)).astype(np.float32)
    x[:] = np.nan  # every batch's loss is NaN from step 1
    y = np.zeros(n, np.int64)
    return x, y


def _cfg(**kw):
    base = dict(batch_size=16, epochs=1, learning_rate=1e-2, log_every=1,
                prefetch_depth=0, donate_state=False)
    base.update(kw)
    return TrainConfig(**base)


def test_fit_arrays_raises_typed_error_at_the_divergence():
    x, y = _nan_xy()
    tr = Trainer(MLP(features=(8,), num_outputs=2),
                 _cfg(nonfinite_loss="raise"))
    with pytest.raises(NonFiniteLossError) as ei:
        tr.fit_arrays(x, y)
    assert ei.value.step == 1 and ei.value.loop == "fit_arrays"
    assert not math.isfinite(ei.value.value)


def test_fit_arrays_event_mode_fires_exactly_once_per_offending_step():
    obs.enable()
    x, y = _nan_xy(32)  # 2 steps, both NaN
    tr = Trainer(MLP(features=(8,), num_outputs=2),
                 _cfg(nonfinite_loss="event", epochs=2))
    tr.fit_arrays(x, y)  # records and continues
    assert len(tr.history) == 4 and all(math.isnan(v) for v in tr.history)
    assert obs.registry().value(
        "train.nonfinite_losses", loop="fit_arrays") == 4
    events = [r for r in obs.captured()
              if getattr(r, "name", "") == "train/nonfinite"]
    assert [e.labels["step"] for e in events] == [1, 2, 3, 4]


def test_fit_stream_event_mode_fires_exactly_once_per_offending_step():
    obs.enable()
    x, y = _nan_xy(32)
    sizes = [5, 11, 3, 13]  # ragged chunks, 32 rows → 2 steps/epoch

    def source():
        off = 0
        for n in sizes:
            yield x[off:off + n], y[off:off + n]
            off += n

    tr = Trainer(MLP(features=(8,), num_outputs=2),
                 _cfg(nonfinite_loss="event", epochs=2))
    tr.fit_stream(source)
    assert len(tr.history) == 4 and all(math.isnan(v) for v in tr.history)
    assert obs.registry().value(
        "train.nonfinite_losses", loop="fit_stream") == 4


def test_fit_stream_raise_mode_dies_at_step_one():
    x, y = _nan_xy(32)
    tr = Trainer(MLP(features=(8,), num_outputs=2),
                 _cfg(nonfinite_loss="raise"))
    with pytest.raises(NonFiniteLossError) as ei:
        tr.fit_stream(iter([(x, y)]))
    assert ei.value.step == 1 and ei.value.loop == "fit_stream"


def test_nonfinite_raise_leaves_a_flight_dump(tmp_path):
    """The run dies AT the divergence WITH forensics: the typed raise
    passes through fit_arrays' crash hook before propagating."""
    flight.enable(str(tmp_path))
    x, y = _nan_xy()
    tr = Trainer(MLP(features=(8,), num_outputs=2), _cfg())
    with pytest.raises(NonFiniteLossError):
        tr.fit_arrays(x, y)
    dumps = glob.glob(os.path.join(str(tmp_path), "flight_crash_*.json"))
    assert len(dumps) == 1
    dump = json.loads(open(dumps[0]).read())
    assert dump["exception"]["type"] == "NonFiniteLossError"
    assert dump["extra"]["context"] == "Trainer.fit_arrays"
    assert any(r["name"] == "train/step" for r in dump["ring"])


# ---- straggler detector ----


def test_straggler_detector_names_the_delayed_host():
    obs.enable()
    det = StragglerDetector("fit_stream", factor=2.0)
    # consumer side accumulates; producer drains the mean
    for ms in (100.0, 110.0, 90.0):
        det.observe(ms)
    assert det.local_mean_ms() == pytest.approx(100.0)
    assert det.local_mean_ms() == 0.0  # drained → the no-data marker
    # host 2 is artificially 3.5× the median → flagged by name
    verdict = det.ingest(np.array([100.0, 110.0, 350.0, 95.0]),
                         process_index=0)
    assert verdict["straggler"] is True and verdict["slow_host"] == 2
    assert verdict["skew"] == pytest.approx((350 - 95) / 350, abs=1e-3)
    reg = obs.registry()
    assert reg.value("train.host_skew", loop="fit_stream") \
        == pytest.approx(verdict["skew"], abs=1e-4)
    assert reg.value("train.host_step_ms", loop="fit_stream",
                     host=2) == 350.0
    assert reg.value("train.stragglers", loop="fit_stream") == 1
    events = [r for r in obs.captured()
              if getattr(r, "name", "") == "train/straggler"]
    assert len(events) == 1 and events[0].labels["host"] == 2
    assert det.last is verdict


def test_straggler_balanced_hosts_and_empty_window():
    obs.enable()
    det = StragglerDetector("fit_stream")
    # balanced: skew published, nobody flagged
    v = det.ingest(np.array([100.0, 105.0, 98.0, 102.0]))
    assert v["straggler"] is False
    assert obs.registry().value("train.stragglers",
                                loop="fit_stream") is None
    # zero-mean hosts (filler-only blocks) are excluded from the
    # baseline; an all-idle window has no verdict
    assert det.ingest(np.zeros(4)) is None
    v = det.ingest(np.array([0.0, 100.0, 101.0, 99.0]))
    assert v["straggler"] is False  # idle host never drags the median


def test_dump_is_strict_json_even_with_nonfinite_metrics(tmp_path):
    """Regression: json.dump emits bare NaN/Infinity tokens (invalid
    JSON) — a dump shipped off-box must parse in strict consumers."""
    rec = flight.enable(str(tmp_path))
    obs.registry().gauge("train.loss").set(float("nan"))
    obs.registry().gauge("train.lr").set(float("inf"))
    path = rec.dump("crash")
    raw = open(path).read()

    def _no_constants(name):
        raise AssertionError(f"non-strict JSON token {name!r} in dump")

    dump = json.loads(raw, parse_constant=_no_constants)
    assert dump["registry"]["gauges"]["train.loss"] == "NaN"
    assert dump["registry"]["gauges"]["train.lr"] == "Infinity"


def test_straggler_flagged_on_a_two_host_mesh():
    """Regression: a self-inclusive median made 2 active hosts
    unflaggable for any factor >= 2 (hi > factor*(hi+lo)/2 has no
    solution) — and 2 processes is the common multi-host config. The
    baseline is now the median of the OTHER active hosts."""
    obs.enable()
    det = StragglerDetector("fit_stream", factor=2.0)
    v = det.ingest(np.array([10.0, 1000.0]))
    assert v["straggler"] is True and v["slow_host"] == 1
    assert v["median_ms"] == 10.0  # the peer, not (10+1000)/2
    # balanced 2-host window stays quiet
    assert det.ingest(np.array([10.0, 11.0]))["straggler"] is False
    # 2 hosts but one idle: no peer baseline, never flagged
    assert det.ingest(np.array([0.0, 50.0]))["straggler"] is False


def test_crash_dump_dedups_on_crash_then_excepthook(tmp_path):
    """Regression: fit loops dump at the failure point (on_crash) and
    re-raise; the same exception then reaches the chained excepthook —
    which must NOT burn a second dump-budget slot on it."""
    rec = flight.enable(str(tmp_path))
    try:
        raise RuntimeError("induced once")
    except RuntimeError as e:
        first = flight.on_crash(e, context="fit")
        assert first is not None
        # the uncaught-exception path fires next with the SAME object
        sys.excepthook(type(e), e, e.__traceback__)
    dumps = glob.glob(os.path.join(str(tmp_path), "flight_crash_*.json"))
    assert len(dumps) == 1, f"duplicate dumps for one exception: {dumps}"
    # a DIFFERENT exception still dumps
    try:
        raise ValueError("another")
    except ValueError as e2:
        assert rec.dump("crash", exc=e2) is not None


# ---- device attribution ----


def test_segment_gauges_and_compile_attribution():
    obs.enable(device=True)
    assert obs_device.enabled()
    bundle = mlp_bundle(6)
    jm = JaxModel(model=bundle, input_col="x", output_col="scores",
                  minibatch_size=8)
    rng = np.random.default_rng(0)
    table = DataTable({"x": list(rng.normal(size=(16, 6))
                                 .astype(np.float32))})
    jm.transform(table)
    snap = obs.registry().snapshot()
    seg_gauges = {k: v for k, v in snap["gauges"].items()
                  if k.startswith("plan.segment.")}
    for kind in ("flops", "bytes", "peak_hbm"):
        keys = [k for k in seg_gauges if f"plan.segment.{kind}" in k]
        assert keys, f"plan.segment.{kind} gauge not populated"
        assert all(seg_gauges[k] >= 0 for k in keys)
    compiles = [v for k, v in snap["counters"].items()
                if k.startswith("plan.xla_compiles")]
    assert compiles and sum(compiles) >= 1
    hists = [k for k in snap["histograms"]
             if k.startswith("plan.compile_ms")]
    assert hists, "compile-time histogram not recorded"
    # warm re-run: no new compile attributed, gauges unchanged
    before = sum(compiles)
    jm.transform(table)
    snap2 = obs.registry().snapshot()
    after = sum(v for k, v in snap2["counters"].items()
                if k.startswith("plan.xla_compiles"))
    assert after == before
    # obs.disable() switches the pillar off with the tracer
    obs.disable()
    assert not obs_device.enabled()


def test_host_split_decomposes_plan_spans():
    obs.enable()
    bundle = mlp_bundle(6)
    jm = JaxModel(model=bundle, input_col="x", output_col="scores",
                  minibatch_size=8)
    rng = np.random.default_rng(0)
    table = DataTable({"x": list(rng.normal(size=(24, 6))
                                 .astype(np.float32))})
    jm.transform(table)
    split = obs.host_phase_split()
    assert split is not None
    assert not any("compute" in k or "idle" in k for k in split)
    parts = sum(v for k, v in split.items()
                if k.endswith("_s") and k != "wall_s")
    assert parts == pytest.approx(split["wall_s"], rel=0.02)
    fr = sum(v for k, v in split.items() if k.endswith("_share"))
    assert fr == pytest.approx(1.0, abs=0.02)
    assert all(split[k] >= 0 for k in split)
    assert split["h2d_s"] > 0 and split["coerce_s"] > 0
    # no boundary spans → no split (never a division by zero)
    obs.clear()
    assert obs.host_phase_split() is None
    assert obs.host_phase_split(records=[]) is None


def test_host_split_is_sane_for_concurrent_serve_lanes():
    """Regression: dp>1 serve lanes emit OVERLAPPING plan/dispatch
    spans; a per-span duration sum reported more than the wall and
    shares > 1. The split measures the union of intervals."""
    from mmlspark_tpu.obs.events import SpanRecord

    def span(name, start_ms, dur_ms, tid):
        return SpanRecord(name, "plan", int(start_ms * 1e6),
                          int(dur_ms * 1e6), tid, f"lane{tid}",
                          tid * 100, None, 0, None)

    # 4 lanes dispatching [0, 10] ms concurrently, then one 2 ms drain
    records = [span("plan/dispatch", 0, 10, t) for t in range(4)]
    records.append(span("plan/d2h", 10, 2, 0))
    split = obs.host_phase_split(records)
    assert split["wall_s"] == pytest.approx(0.012)
    assert split["dispatch_s"] == pytest.approx(0.010)  # union, not 40
    assert split["fetch_wait_s"] == pytest.approx(0.002)
    total_share = sum(split[k] for k in split if k.endswith("_share"))
    assert total_share == pytest.approx(1.0, abs=0.01)
    # h2d nested in dispatch still subtracts from dispatch, once
    records = [span("plan/dispatch", 0, 10, t) for t in range(2)]
    records += [span("plan/h2d", 0, 3, t) for t in range(2)]
    split = obs.host_phase_split(records)
    assert split["h2d_s"] == pytest.approx(0.003)
    assert split["dispatch_s"] == pytest.approx(0.007)


def test_poll_memory_never_initializes_a_backend():
    """Regression: ``jax.local_devices()`` INITIALIZES the default
    backend — fatal for a headless-forensics process that imports jax
    early but calls ``jax.distributed.initialize()`` later. The watchdog
    poll must stay a no-op until the app brings a backend up itself."""
    import subprocess
    code = (
        "import sys; sys.path.insert(0, %r)\n"
        "import jax\n"
        "from jax._src import xla_bridge as xb\n"
        "from mmlspark_tpu import obs\n"
        "obs.enable(device=True)\n"
        "out = obs.poll_memory()\n"
        "assert out == {}, out\n"
        "assert not xb.backends_are_initialized(), "
        "'poll_memory initialized the backend'\n"
        "print('OK')\n" % os.path.join(os.path.dirname(__file__), ".."))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0 and "OK" in r.stdout, r.stderr


def test_poll_memory_is_dryrun_safe():
    # CPU devices report no memory_stats: the poll is a quiet no-op that
    # publishes nothing and never raises (the watchdog calls this)
    out = obs.poll_memory()
    assert isinstance(out, dict)
    snap = obs.registry().snapshot()
    for key in snap["gauges"]:
        assert not key.startswith("device.mem_") or out, (
            "memory gauges appeared without any device reporting stats")


def test_env_flag_precedence_enable_kwargs_override():
    """obs.enable(device=...) after an env-style enable() overrides it —
    the documented precedence (the env is read once at import)."""
    obs.enable()  # the MMLSPARK_TPU_OBS=1 path
    assert not obs_device.enabled()
    obs.enable(device=True)  # explicit kwargs win
    assert obs_device.enabled()
    obs.disable()
    assert not obs_device.enabled()
