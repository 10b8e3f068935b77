"""The harness's own tests: run by hand, on the CPU, at tiny test-only sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

Not collected by the repo's tier-1 run (``tests/``). They drive both
drivers end to end through ``run.run`` with the device check bypassed here
(the command line cannot bypass it), plant each fault a cell can have under
the timed path and see ``correct`` come out false, put the lower-precision
control in the program's place, and check the yardstick's arithmetic
(``flops.py``, ``trace_reduce.py``).
"""

from __future__ import annotations

import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import flops, trace_reduce  # noqa: E402

RESULT_KEYS = {"correct", "attempted", "failed", "metrics", "device"}
TINY = dict(manifest_path=os.path.join(TESTS, "tiny_manifest.json"),
            workloads_dir=os.path.join(TESTS, "workloads"),
            device_check=False)
CELLS = {"tiny_vit.b8": ("train_step_ms",
                         {"input_wait_share.train", "train_mfu",
                          "device_idle_share.train"}),
         "tiny_resnet.table40": ("score_rows_per_s",
                                 {"score_mfu", "device_idle_share.score"})}


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location(
        "benchmark_run", os.path.join(BENCH, "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def run_cell(bench, cell: str, seed: int = 3000000019, trace: int = 0):
    return bench.run(["--workload", cell, "--seed", str(seed), "--seconds",
                      "1", "--trace", str(trace)], **TINY)


# ---- both drivers, end to end ----

@pytest.mark.parametrize("cell", sorted(CELLS))
def test_untraced_run_reports_the_end_to_end_metrics(bench, cell):
    result = json.loads(json.dumps(run_cell(bench, cell)))   # one JSON line
    assert set(result) == RESULT_KEYS | {"compared"}
    assert list(result)[-1] == "compared"
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {CELLS[cell][0], "setup_s"}
    for metric in result["metrics"].values():
        assert set(metric) == {"value", "unit"} and metric["value"] > 0
    assert set(result["device"]) == {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    for entry in result["compared"].values():
        assert set(entry) == {"value", "limit"}


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_traced_run_reports_the_per_layer_metrics(bench, cell, monkeypatch):
    # the CPU's trace has no device plane: hand the reduction three
    # operations; the slice begins 10 ms (the tiny cells' settle_s) after
    # the first starts and lasts 150 ms (0.15 of the window's second), so
    # it cuts the second operation short and leaves the third out
    events = [("%fusion.0 = f32[1]{0} fusion(...)", 0, 5_000_000),
              ("%fusion.1 = bf16[8,4]{1,0} fusion(...)", 20_000_000,
               40_000_000),
              ("%fusion.2 = f32[2]{0} fusion(...)", 110_000_000, 90_000_000),
              ("%fusion.3 = f32[2]{0} fusion(...)", 300_000_000, 10_000_000)]
    monkeypatch.setattr(trace_reduce, "load_device_events",
                        lambda path: {"/device:TPU:0": events})
    monkeypatch.setattr(bench, "check_device", lambda chips: (
        bench.describe_device(), {"bf16_flops_per_s": 1e12}))
    result = bench.run(["--workload", cell, "--seed", "7", "--seconds", "1",
                        "--trace", "1"], **{**TINY, "device_check": True})
    assert set(result) == RESULT_KEYS | {"breakdown", "compared"}
    assert set(result["metrics"]) == CELLS[cell][1]
    assert result["device"]["busy_s"] == pytest.approx(0.09)
    assert result["device"]["window_s"] == pytest.approx(0.15)
    assert result["breakdown"]["idle_gaps"] == [["unattributed", 0.05],
                                                ["unattributed", 0.01]]
    assert [n for n, _ in result["breakdown"]["device_ops"]] == [
        "%fusion.2 f32[2]", "%fusion.1 bf16[8,4]"]
    idle = [k for k in result["metrics"] if k.startswith("device_idle")]
    assert result["metrics"][idle[0]]["value"] == pytest.approx(40.0)


def test_a_reader_with_nothing_to_read_leaves_its_metric_out(bench):
    reader = bench.load_file_module("layer_metrics",
                                    "device_idle_share.train")
    assert reader.read({"trace": {"busy_s": 0.0, "window_s": 1.0}}) is None
    assert reader.read({"trace": None}) is None
    reader = bench.load_file_module("layer_metrics", "train_mfu")
    config = bench.load_json(os.path.join(TESTS, "tiny_vit.json"))
    assert reader.read({"config": config, "window": {"rows": 0},
                        "peaks": None, "chips": 1}) is None


def test_the_command_refuses_without_a_tpu():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload",
         "vit_b16_finetune.b128", "--seed", "1", "--seconds", "1",
         "--trace", "0"], capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=ROOT)
    assert out.returncode != 0
    assert out.stdout.strip() == ""
    assert "not a TPU" in out.stderr


def test_an_unknown_device_kind_is_refused(bench, monkeypatch):
    import jax

    class Fake:
        platform, device_kind = "tpu", "TPU v99"
    monkeypatch.setattr(jax, "devices", lambda *a: [Fake()])
    with pytest.raises(bench.Refused, match="peaks.json"):
        bench.check_device(1)


def test_the_committed_manifest_names_files_that_exist(bench):
    manifest = bench.load_json(bench.MANIFEST)
    for cell in manifest["workloads"]:
        wl = bench.load_json(os.path.join(BENCH, "workloads",
                                          cell["name"] + ".json"))
        assert os.path.isfile(os.path.join(BENCH, "drivers",
                                           wl["driver"] + ".py"))
        assert wl["chips"] == cell["chips"]
        assert wl["config"] == cell["config"]
    for cfg in manifest["configs"]:
        assert os.path.isfile(os.path.join(ROOT, cfg["file"]))
    for metric in manifest["per_layer"]:
        assert os.path.isfile(os.path.join(BENCH, "layer_metrics",
                                           metric["name"] + ".py"))


# ---- the timed path broken underneath: correct has to come out false ----

def break_train_step(monkeypatch, fault: str):
    import jax
    import jax.numpy as jnp

    from mmlspark_tpu.train import loop

    real_make = loop.make_train_step

    def make(module, cfg, mesh):
        init_state, step, step_masked = real_make(module, cfg, mesh)
        real = step_masked.__wrapped__
        if fault == "state_unchanged":
            def broken(state, x, y, w):
                return state, real(state, x, y, w)[1]
        elif fault == "half_batch":
            def broken(state, x, y, w):
                keep = jnp.arange(w.shape[0]) < w.shape[0] // 2
                return real(state, x, y, w * keep)
        return init_state, step, jax.jit(broken)

    monkeypatch.setattr(loop, "make_train_step", make)


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch"])
def test_a_broken_train_step_is_not_correct(bench, monkeypatch, fault):
    break_train_step(monkeypatch, fault)
    result = run_cell(bench, "tiny_vit.b8")
    assert result["correct"] is False, result["compared"]


@pytest.mark.parametrize("fault", ["answer_altered", "rows_swapped"])
def test_a_broken_scoring_call_is_not_correct(bench, monkeypatch, fault):
    from mmlspark_tpu.core import plan

    real = plan.pipeline_minibatches

    def broken(*args, **kwargs):
        outs = real(*args, **kwargs)
        scores = np.array(outs[0])
        if fault == "answer_altered":
            scores[0, 0] += 1.0
        else:       # the first rows of two minibatches change places
            size = args[3]
            scores[[0, size]] = scores[[size, 0]]
        return [scores] + list(outs[1:])

    import mmlspark_tpu.models.jax_model as jm
    monkeypatch.setattr(jm, "pipeline_minibatches", broken)
    result = run_cell(bench, "tiny_resnet.table40")
    assert result["correct"] is False, result["compared"]


# ---- the control: a lower precision in the program's place ----

@pytest.mark.parametrize("cell", sorted(CELLS))
def test_the_lower_precision_control_fails_a_limit(bench, cell):
    _, entry, config, workload = bench.load_cell(
        cell, TINY["manifest_path"], TINY["workloads_dir"])
    driver = bench.load_file_module("drivers", workload["driver"])
    failed = 0
    for seed in (1, 2, 2147483659):
        ctx = bench.Context(entry, config, workload, None, seed, 0.3, False)
        state = driver.setup(ctx)
        driver.measure(ctx, state)
        driver.release(state)
        reference = driver.reference_readings(ctx, state)
        control = driver.reference_readings(ctx, state,
                                            quant="float8_e4m3fn")
        numbers = driver.compare(control, reference)
        limits = workload["limits"]
        failed += any(v > limits[k.split("_step")[0]]
                      for k, v in numbers.items())
    assert failed == 3


# ---- the yardstick's arithmetic ----

def test_flops_reproduce_the_check_values():
    vit = json.load(open(os.path.join(BENCH, "configs",
                                      "vit_b16_finetune.json")))
    parts = flops.vit_forward_flops(vit)
    assert parts["total"] == pytest.approx(35.1e9, rel=0.02)
    assert parts["block_weights"] == pytest.approx(33.5e9, rel=0.02)
    assert parts["patch_embed"] == pytest.approx(0.23e9, rel=0.02)
    assert parts["attention"] == pytest.approx(1.43e9, rel=0.02)
    assert flops.train_flops(vit) == pytest.approx(105e9, rel=0.02)
    resnet = json.load(open(os.path.join(BENCH, "configs",
                                         "resnet50_infer.json")))
    assert flops.forward_flops(resnet) == pytest.approx(8.2e9, rel=0.02)


def test_trace_reduction_on_synthetic_events():
    ms = 1_000_000
    events = [("matmul", 0, 10 * ms),
              ("softmax", 5 * ms, 10 * ms),        # overlaps matmul
              ("loop", 30 * ms, 20 * ms),
              ("matmul", 32 * ms, 5 * ms),         # nested in loop
              ("copy", 80 * ms, 1 * ms)]
    got = trace_reduce.reduce_events(events)
    assert got["busy_s"] == pytest.approx(0.036)   # 15 + 20 + 1 ms
    assert got["device_ops"][:3] == [["loop", 0.02], ["matmul", 0.015],
                                     ["softmax", 0.01]]
    assert got["idle_gaps"] == [["unattributed", 0.03],
                                ["unattributed", 0.015]]
    assert trace_reduce.reduce_events([])["busy_s"] == 0.0
