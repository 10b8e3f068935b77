"""The set-up readers (``benchmark/setup_read.py``): run by hand, on the CPU,
at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

``setup_manifest.json`` is ``span_manifest.json`` with the nine readers of
the program's compile tier added for the same two tiny cells. Every such
reader gives a number after a traced run with the program's tracer never
enabled; what was traced and lowered and what was compiled fit inside the
run's set-up, nested traces counted once; a steady scoring window compiles
nothing; and each returns ``None`` once the ring holds no record of the
window, once it is full, or when the program has no compile tier.
"""

from __future__ import annotations

import os

import pytest

from test_harness import BENCH, TESTS, TINY, bench  # noqa: F401
from benchmark import setup_read, trace_reduce

SETUP = {**TINY, "manifest_path": os.path.join(TESTS, "setup_manifest.json"),
         "device_check": True}
EVERY = {"before_first_program_s.setup", "trace_lower_s.setup",
         "backend_compile_s.setup", "cache_miss_programs.setup",
         "programs_built.setup"}
CELLS = {"tiny_resnet.table40":
         EVERY | {"compiles_in_window.score", "gc_pause_share.score"},
         "tiny_vit.b8":
         EVERY | {"compiles_in_window.train", "gc_pause_share.train"}}
WINDOW = {"tiny_resnet.table40": {"window_s": 1.0, "calls": 3},
          "tiny_vit.b8": {"window_s": 1.0, "steps": 3}}


@pytest.fixture()
def traced(bench, monkeypatch):  # noqa: F811
    """A traced run of a tiny cell on the CPU (``test_span_metrics.py``'s),
    which also hands back what ``run.py`` said on standard error."""
    from mmlspark_tpu import obs

    assert not obs.enabled()
    obs.clear()
    events = [("%fusion.0 = f32[1]{0} fusion(...)", 0, 30_000_000),
              ("%fusion.1 = f32[1]{0} fusion(...)", 60_000_000, 90_000_000)]
    monkeypatch.setattr(trace_reduce, "load_device_events",
                        lambda path: {"/device:TPU:0": events})
    monkeypatch.setattr(bench, "check_device", lambda chips: (
        bench.describe_device(), {"bf16_flops_per_s": 1e12}))
    said: list = []
    monkeypatch.setattr(bench, "say", said.append)

    def run(cell: str) -> tuple:
        result = bench.run(["--workload", cell, "--seed", "3000000023",
                            "--seconds", "1", "--trace", "1"], **SETUP)
        setup_s = next(float(line.split()[1]) for line in said
                       if line.startswith("set-up "))
        return result["metrics"], setup_s
    return run


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_every_setup_reader_gives_a_number(traced, cell):
    metrics, setup_s = traced(cell)
    assert CELLS[cell] <= set(metrics)
    for name in CELLS[cell]:
        assert metrics[name]["value"] >= 0, name
    value = {n: metrics[n]["value"] for n in CELLS[cell]}
    # nested traces are counted once: the parts fit inside the whole. The
    # process here is pytest's, far older than the run, so the machine's
    # part is not compared
    assert 0 < value["trace_lower_s.setup"] \
        + value["backend_compile_s.setup"] < setup_s
    assert value["before_first_program_s.setup"] > 0
    assert 0 <= value["cache_miss_programs.setup"] \
        <= value["programs_built.setup"]
    assert value["programs_built.setup"] >= 1
    for name in value:
        if name.startswith("gc_pause_share"):
            assert value[name] < 100.0


def test_a_steady_scoring_window_compiles_nothing(traced):
    metrics, _ = traced("tiny_resnet.table40")
    assert metrics["compiles_in_window.score"]["value"] == 0


def test_the_tiny_training_window_count_is_pinned(traced):
    """Pinned as found: 0. The feed's batches are made before the window
    and the step was compiled by the driver's first steps, so a training
    window that traces anything has met a new shape."""
    metrics, _ = traced("tiny_vit.b8")
    assert metrics["compiles_in_window.train"]["value"] == 0


@pytest.mark.parametrize("cell", sorted(CELLS))
@pytest.mark.parametrize("state", ["cleared", "full", "no_tier"])
def test_nothing_sound_to_read_no_metric(bench, monkeypatch, cell,  # noqa: F811
                                         state):
    """A ring cleared after the run holds no record of the window; a full
    ring may have lost records of set-up; a parent commit has no tier."""
    from mmlspark_tpu import obs
    from mmlspark_tpu.obs import runtime

    obs.clear()
    if state != "cleared":
        # a window the readers can find, and a program built before it
        import jax
        import jax.numpy as jnp

        jax.jit(lambda x: x + 2.5)(jnp.ones(3)).block_until_ready()
        names = (["transform"] if "calls" in WINDOW[cell]
                 else ["train/step"]) * 3
        for name in names:
            with obs.boundary_span(name, "t"):
                pass
        run = {"window": WINDOW[cell]}
        assert setup_read.setup_records(run)
        if state == "full":
            # a ring exactly as long as what it holds: nothing lost yet,
            # but nothing says so
            obs.enable(buffer_size=runtime.captured_count())
            obs.disable()
            assert runtime.ring_full()
        else:
            monkeypatch.setattr(setup_read, "_tier", lambda: None)
    try:
        for name in CELLS[cell]:
            reader = bench.load_file_module("layer_metrics", name)
            assert reader.read({"window": WINDOW[cell]}) is None, name
    finally:
        obs.enable()            # the default ring again
        obs.disable()
        obs.clear()
