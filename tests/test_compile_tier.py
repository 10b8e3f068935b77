"""The compile tier (obs/compile_tier.py): JAX's own trace / lower /
compile events and the collector's pauses as records in the tracer's one
ring, on its one clock, with the tracer off.

The contracts under test:

* a fresh ``jit`` leaves one ``jit/trace``, ``jit/lower``, ``jit/compile``
  each, named by function, stamped inside the call's wall; a steady call
  leaves nothing and moves no counter;
* a compile under a boundary root carries the root;
* ``cached`` tells a persistent-cache load from a compile;
* a full collection leaves ``host/gc``, a short young one nothing, and
  the callback never waits for the ring's lock;
* ``host_phase_split`` gives a pause and a compile their own phases and
  is unchanged where there is neither;
* ``compile_report`` sums by function;
* obs imports without jax, and the listeners go in once.
"""

import gc
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))

from test_plan import mlp_bundle  # noqa: E402

from mmlspark_tpu import obs
from mmlspark_tpu.data.table import DataTable
from mmlspark_tpu.models.jax_model import JaxModel
from mmlspark_tpu.obs import compile_tier as tier
from mmlspark_tpu.obs import runtime as obs_rt
from mmlspark_tpu.obs.events import SpanRecord

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
# the events are stamped with time.time(): 238 ns of float, and whatever
# the wall clock was slewed against the monotonic one since import
CLOCK_SLACK_NS = 1_000_000


@pytest.fixture(autouse=True)
def tracer_off_and_clean():
    obs.disable()
    obs.clear()
    obs.registry().reset()
    yield
    obs.disable()
    obs.clear()
    obs.registry().reset()


def fresh_jit(name):
    """A jitted function no test has compiled, called ``name``."""
    def fn(x):
        return x * 3.0 + 1.0
    fn.__name__ = fn.__qualname__ = name
    return jax.jit(fn)


def records_of(fun):
    return [r for r in tier.tier_records()
            if (r.labels or {}).get("fun") == fun]


def jit_counters():
    return {k: v for k, v in obs.registry().snapshot()["counters"].items()
            if k.startswith("jit.")}


def rec(name, start_ms, dur_ms, tid=1, labels=None):
    return SpanRecord(name, "t", int(start_ms * 1e6), int(dur_ms * 1e6),
                      tid, f"t{tid}", 0, None, 0, labels)


# ---- the three compile records ----

def test_a_fresh_jit_leaves_one_record_of_each_kind_inside_the_call():
    assert not obs.enabled()
    fn, x = fresh_jit("tier_fresh"), jnp.ones(8)
    t0 = time.perf_counter_ns()
    fn(x).block_until_ready()
    t1 = time.perf_counter_ns()
    got = records_of("tier_fresh")
    assert [r.name for r in got] == list(tier.JIT_NAMES)
    for r in got:
        assert r.cat == "jit" and r.dur_ns > 0
        assert t0 - CLOCK_SLACK_NS <= r.start_ns <= r.end_ns \
            <= t1 + CLOCK_SLACK_NS
        assert abs(r.start_epoch_ns - time.time_ns()) < 60e9
    trace, lower, comp = got
    assert trace.end_ns <= lower.start_ns + CLOCK_SLACK_NS
    assert lower.end_ns <= comp.start_ns + CLOCK_SLACK_NS
    assert comp.labels["cached"] is False
    assert "cached" not in trace.labels and "cached" not in lower.labels
    counters = jit_counters()
    assert counters["jit.traces"] >= 1 and counters["jit.cache_misses"] >= 1
    for key in ("jit.trace_s", "jit.lower_s", "jit.compile_s"):
        assert counters[key] > 0


def test_a_thousand_steady_calls_add_no_record_and_move_no_counter():
    fn, x = fresh_jit("tier_steady"), jnp.ones(8)
    fn(x).block_until_ready()
    before = [r for r in tier.tier_records() if r.name != tier.GC]
    counters = jit_counters()
    for _ in range(1000):
        out = fn(x)
    out.block_until_ready()
    after = [r for r in tier.tier_records() if r.name != tier.GC]
    assert len(after) == len(before)
    assert jit_counters() == counters


@pytest.mark.parametrize("under_root", [True, False])
def test_a_compile_names_the_call_it_stalled(under_root):
    fn, x = fresh_jit(f"tier_root_{under_root}"), jnp.ones(8)
    if under_root:
        with obs.boundary_span("transform", "plan") as root:
            with obs.boundary_span("plan/dispatch", "plan") as inner:
                fn(x).block_until_ready()
        want = (root._span_id, inner._span_id, 2)
    else:
        fn(x).block_until_ready()
        want = (None, None, 0)
    got = records_of(f"tier_root_{under_root}")
    assert len(got) == 3
    assert {(r.root_id, r.parent_id, r.depth) for r in got} == {want}


@pytest.fixture()
def persistent_cache(tmp_path):
    """jax's persistent compile cache in a directory of this test's."""
    from jax.experimental.compilation_cache import compilation_cache as cc

    keys = {"jax_compilation_cache_dir": str(tmp_path),
            "jax_persistent_cache_min_compile_time_secs": 0.0,
            "jax_persistent_cache_min_entry_size_bytes": -1}
    old = {k: getattr(jax.config, k) for k in keys}
    for k, v in keys.items():
        jax.config.update(k, v)
    cc.reset_cache()
    yield
    for k, v in old.items():
        jax.config.update(k, v)
    cc.reset_cache()


def test_cached_tells_a_cache_load_from_a_compile(persistent_cache):
    fn, x = fresh_jit("tier_cached"), jnp.ones(8)
    fn(x).block_until_ready()
    jax.clear_caches()
    fn(x).block_until_ready()
    compiles = [r for r in records_of("tier_cached")
                if r.name == tier.COMPILE]
    assert [r.labels["cached"] for r in compiles] == [False, True]
    counters = jit_counters()
    assert counters["jit.cache_hits"] >= 1
    row = next(r for r in obs.compile_report() if r["fun"] == "tier_cached")
    assert (row["traces"], row["compiles"], row["cached"]) == (2, 2, 1)


def test_the_dispatch_seam_reads_its_compile_off_the_tier():
    """``plan.compile_ms`` is the jit/compile records inside the
    dispatch spans: compile alone, not the first call's wall."""
    obs.enable(device=True)
    jm = JaxModel(model=mlp_bundle(5), input_col="x", output_col="scores",
                  minibatch_size=8)
    rng = np.random.default_rng(0)
    table = DataTable({"x": list(rng.normal(size=(16, 5))
                                 .astype(np.float32))})
    jm.transform(table)
    spans = {r.span_id: r for r in obs_rt.span_records()}
    inside = [r for r in spans.values() if r.name == tier.COMPILE
              and r.parent_id in spans
              and spans[r.parent_id].name == "plan/dispatch"]
    assert inside
    snap = obs.registry().snapshot()
    hists = [v for k, v in snap["histograms"].items()
             if k.startswith("plan.compile_ms")]
    assert sum(h["sum"] for h in hists) == pytest.approx(
        sum(r.dur_ns for r in inside) / 1e6, abs=1e-3)
    assert sum(v for k, v in snap["counters"].items()
               if k.startswith("plan.xla_compiles")) == len(inside)
    dispatch_ms = sum(r.dur_ns for r in spans.values()
                      if r.name == "plan/dispatch") / 1e6
    assert sum(h["sum"] for h in hists) < dispatch_ms


# ---- the collector ----

@pytest.fixture()
def collector_quiet():
    """No automatic collection while a test counts them."""
    gc.collect()
    gc.disable()
    obs.clear()
    yield
    gc.enable()


@pytest.mark.parametrize("generation, written", [(2, 1), (0, 0)])
def test_a_full_collection_is_recorded_and_a_short_young_one_not(
        collector_quiet, generation, written):
    t0 = time.perf_counter_ns()
    gc.collect(generation)
    t1 = time.perf_counter_ns()
    got = [r for r in obs.captured() if r.name == tier.GC]
    assert len(got) == written
    for r in got:
        assert r.cat == "host" and r.labels["generation"] == generation
        assert "collected" in r.labels
        assert t0 <= r.start_ns <= r.end_ns <= t1


def test_a_collection_under_the_rings_lock_is_dropped_not_waited_for(
        collector_quiet):
    dropped = obs.gc_records_dropped()
    assert obs_rt._lock.acquire(timeout=5)
    try:
        gc.collect()            # would deadlock if the callback waited
    finally:
        obs_rt._lock.release()
    assert obs.gc_records_dropped() == dropped + 1
    assert not [r for r in obs.captured() if r.name == tier.GC]
    gc.collect()
    assert len([r for r in obs.captured() if r.name == tier.GC]) == 1
    assert obs.gc_records_dropped() == dropped + 1


def test_a_pause_carries_the_boundary_root_it_interrupted(collector_quiet):
    with obs.boundary_span("transform", "plan") as root:
        gc.collect()
    pause = next(r for r in obs.captured() if r.name == tier.GC)
    assert pause.root_id == pause.parent_id == root._span_id


# ---- host_phase_split ----

BASE = [("transform", 0, 100), ("plan/dispatch", 10, 20),
        ("plan/h2d", 12, 5), ("plan/d2h", 40, 50)]
BASE_SPLIT = {"h2d_s": 0.005, "dispatch_s": 0.015, "fetch_wait_s": 0.050,
              "gc_s": 0.0, "jit_s": 0.0, "unspanned_s": 0.030}


@pytest.mark.parametrize("extra, moved", [
    ([], {}),
    # a pause inside plan/d2h is a pause, not fetch wait
    ([("host/gc", 50, 10)], {"gc_s": 0.010, "fetch_wait_s": 0.040}),
    # a compile inside plan/dispatch is a compile, not dispatch
    ([("jit/compile", 18, 8)], {"jit_s": 0.008, "dispatch_s": 0.007}),
    # a trace holding its lowering is counted once; the pause wins
    ([("jit/trace", 20, 8), ("jit/lower", 22, 2), ("host/gc", 24, 2)],
     {"jit_s": 0.006, "gc_s": 0.002, "dispatch_s": 0.007}),
    # outside the boundary spans' wall: neither stretches it nor counts
    ([("jit/compile", -50, 30), ("host/gc", 120, 10)], {}),
])
def test_host_phase_split_names_pauses_and_compiles(extra, moved):
    split = obs.host_phase_split([rec(*r) for r in BASE + extra])
    assert split["wall_s"] == pytest.approx(0.100)
    for key, value in {**BASE_SPLIT, **moved}.items():
        assert split[key] == pytest.approx(value, abs=1e-9), key
    shares = sum(v for k, v in split.items() if k.endswith("_share"))
    assert shares == pytest.approx(1.0, abs=1e-9)


def test_tier_records_alone_make_no_split():
    assert obs.host_phase_split([rec("jit/compile", 0, 5),
                                 rec("host/gc", 1, 1)]) is None


# ---- compile_report ----

def test_compile_report_sums_by_function_largest_first():
    records = [
        rec("jit/trace", 0, 10, labels={"fun": "outer"}),
        rec("jit/trace", 2, 4, labels={"fun": "inner"}),
        rec("jit/lower", 10, 5, labels={"fun": "outer"}),
        rec("jit/compile", 15, 50,
            labels={"fun": "outer", "cached": False}),
        rec("jit/trace", 70, 10, labels={"fun": "outer"}),
        rec("jit/compile", 80, 1, labels={"fun": "outer", "cached": True}),
        rec("host/gc", 90, 3, labels={"generation": 2, "collected": 0}),
        rec("plan/h2d", 95, 1),
    ]
    outer, inner = obs.compile_report(records)
    assert outer == {"fun": "outer", "traces": 2,
                     "trace_s": pytest.approx(0.020),
                     "lower_s": pytest.approx(0.005), "compiles": 2,
                     "compile_s": pytest.approx(0.051), "cached": 1}
    assert inner["fun"] == "inner" and inner["traces"] == 1
    assert inner["trace_s"] == pytest.approx(0.004)
    # the rows do not add up (inner was traced under outer): a union does
    assert tier.union_seconds(records, (tier.TRACE, tier.LOWER)) \
        == pytest.approx(0.025)
    assert tier.union_seconds(records, (tier.COMPILE,)) \
        == pytest.approx(0.051)


def test_a_module_name_is_unwrapped_so_trace_and_compile_share_a_row():
    fn = fresh_jit("tier_row")
    fn(jnp.ones(8)).block_until_ready()
    row = next(r for r in obs.compile_report() if r["fun"] == "tier_row")
    assert (row["traces"], row["compiles"]) == (1, 1)
    assert row["trace_s"] > 0 and row["lower_s"] > 0 and row["compile_s"] > 0


# ---- registration, the clock, process start ----

def test_the_listeners_go_in_once():
    from jax._src import monitoring

    assert tier.register() and tier.register()
    assert monitoring.get_event_time_span_listeners().count(
        tier._on_time_span) == 1
    assert monitoring.get_event_listeners().count(tier._on_event) == 1
    assert gc.callbacks.count(tier._on_gc) == 1


def test_epoch_and_span_clock_are_inverse():
    now = time.perf_counter_ns()
    assert obs_rt.from_epoch_ns(obs_rt.to_epoch_ns(now)) == now


def test_obs_imports_without_jax_and_registers_when_the_cache_is_placed():
    """A host-only process pays no jax import for obs; a process that
    compiles registers the listeners through ``place_compilation_cache``.
    The child also reports when the kernel started it."""
    code = (
        "import sys, gc\n"
        "from mmlspark_tpu import obs\n"
        "from mmlspark_tpu.obs import compile_tier as tier, runtime\n"
        "assert 'jax' not in sys.modules and not tier._registered\n"
        "assert gc.callbacks.count(tier._on_gc) == 1\n"
        "with obs.boundary_span('edge', 't'):\n"
        "    pass\n"
        "from mmlspark_tpu.utils.jit_cache import place_compilation_cache\n"
        "place_compilation_cache()\n"
        "assert tier._registered and tier.register()\n"
        "print(runtime.process_start_epoch_ns())\n")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    before = time.time_ns()
    done = subprocess.run([sys.executable, "-c", code], env=env, cwd=REPO,
                          capture_output=True, text=True, timeout=120)
    after = time.time_ns()
    assert done.returncode == 0, done.stderr
    started = int(done.stdout.strip().splitlines()[-1])
    # starttime counts clock ticks: 10 ms of resolution, rounded down
    assert before - 20_000_000 <= started <= after


def test_process_start_precedes_the_clock_anchor():
    started = obs_rt.process_start_epoch_ns()
    assert started is not None
    assert 0 < obs_rt.CLOCK_ANCHOR[1] - started < 86_400e9
