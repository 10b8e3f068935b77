"""The ``pipeline_score`` driver on the CPU at tiny test-only sizes (run by
hand, like ``test_harness.py``):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_pipeline_score.py -q -p no:cacheprovider

The cell is ``tiny_resnet.pipeline`` of ``pipeline_manifest.json``: 40 JPEG
rows through decode, resize, unroll and the tiny ResNet cut to its pooled
features, added as files. The driver runs end to end through ``run.run``;
the lower-precision control and both planted faults fail a limit; the
accepted host-span readers find the fused segment's records.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

import numpy as np
import pytest

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)

from benchmark import trace_reduce  # noqa: E402

CELL = "tiny_resnet.pipeline"
TINY = dict(manifest_path=os.path.join(TESTS, "pipeline_manifest.json"),
            workloads_dir=os.path.join(TESTS, "workloads"),
            device_check=False)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location(
        "benchmark_run", os.path.join(BENCH, "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def test_untraced_run_is_correct_and_reports_rows_per_second(bench):
    result = json.loads(json.dumps(bench.run(
        ["--workload", CELL, "--seed", "3000000019", "--seconds", "1",
         "--trace", "0"], **TINY)))
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"score_rows_per_s", "setup_s"}
    assert set(result["compared"]) == {"logit_gap_max", "logit_gap_rms",
                                       "rows_missing"}


def test_traced_run_reports_every_metric_the_cell_lists(bench, monkeypatch):
    ms = 1_000_000
    events = [("%fusion.9 = f32[4,32]{1,0} fusion(...)", 60 * ms, 200 * ms)]
    monkeypatch.setattr(trace_reduce, "load_device_events",
                        lambda path: {"/device:TPU:0": events})
    monkeypatch.setattr(bench, "check_device", lambda chips: (
        bench.describe_device(), dict(PEAKS)))
    result = bench.run(["--workload", CELL, "--seed", "7", "--seconds", "1",
                        "--trace", "1"], **{**TINY, "device_check": True})
    listed = {m["name"] for m in json.load(open(TINY["manifest_path"]))[
        "per_layer"]}
    assert set(result["metrics"]) == listed
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    # decoded rows are separate allocations: every coerced row is copied
    assert metrics["coerce_view_share.score"] == 0.0
    # uint8 pixels of the resized image, the padded tail included
    assert metrics["h2d_bytes_per_row.score"] >= 32 * 32 * 3
    # decode and resize run on the host outside any boundary span
    assert metrics["unspanned_share.score"] > 10.0


@pytest.fixture(scope="module")
def readings(bench):
    _, entry, config, workload = bench.load_cell(
        CELL, TINY["manifest_path"], TINY["workloads_dir"])
    driver = bench.load_file_module("drivers", workload["driver"])
    ctx = bench.Context(entry, config, workload, None, 2147483659, 0.3, False)
    state = driver.setup(ctx)
    driver.measure(ctx, state)
    program = driver.program_readings(state)
    driver.release(state)
    return driver, ctx, state, program, driver.reference_readings(ctx, state)


def fails(driver, workload, broken, reference) -> bool:
    numbers = driver.compare(broken, reference)
    return any(numbers[k] > limit for k, limit in workload["limits"].items()
               if k in numbers)


def test_the_program_passes_and_the_control_and_the_fault_fail(readings):
    driver, ctx, state, program, reference = readings
    assert program.shape == reference.shape
    assert program.shape[1] == 16 * 2 * 4        # the pooled features
    assert not fails(driver, ctx.workload, program, reference)
    control = driver.reference_readings(ctx, state, quant="float8_e4m3fn")
    assert fails(driver, ctx.workload, control, reference)
    assert driver.FAULTS == ("rows_shifted", "channels_swapped")
    for fault in driver.FAULTS:
        broken = driver.reference_readings(ctx, state, fault=fault)
        assert fails(driver, ctx.workload, broken, reference), fault


def test_the_reference_sees_pixels_the_program_did_not_prepare(readings):
    """``plain_pixels`` uses OpenCV's decoder and a resize written from the
    definition; the program's first stage agrees with it to one count (a
    half rounded in float32 or in float64)."""
    driver, ctx, state, _, _ = readings
    rows = state["scorer"].data[:8]
    col = ctx.workload["input_col"]
    staged = driver.resize_stage(ctx).transform(
        driver.bytes_table(ctx, rows))[col]
    program = np.stack([np.asarray(v["data"]) for v in staged])
    plain = driver.plain_pixels(ctx, rows)
    assert plain.shape == program.shape and plain.dtype == np.uint8
    gap = np.abs(program.astype(int) - plain.astype(int))
    assert gap.max() <= 1 and (gap > 0).mean() < 1e-3
    # corners on corners: the four corner pixels are the source's own
    square = np.arange(5 * 5 * 3, dtype=np.uint8).reshape(5, 5, 3)
    out = driver.resize_align_corners(square, 9)
    assert (out[::2, ::2] == square).all()
    assert (out[1, 0] == (square[0, 0].astype(int) + square[1, 0] + 1) // 2).all()


def test_every_row_is_its_own_jpeg_and_the_seed_decides_them(bench):
    _, entry, config, workload = bench.load_cell(
        CELL, TINY["manifest_path"], TINY["workloads_dir"])
    driver = bench.load_file_module("drivers", workload["driver"])
    rows = [driver.make_rows(bench.Context(entry, config, workload, None,
                                           seed, 1.0, False))
            for seed in (5, 5, 6)]
    assert rows[0] == rows[1] and rows[0] != rows[2]
    assert len(set(rows[0])) == workload["rows"]
    assert all(isinstance(r, bytes) and r[:2] == b"\xff\xd8" for r in rows[0])
    from mmlspark_tpu.data.readers import decode_image
    image = decode_image(rows[0][0])
    assert image.shape == (40, 40, 3) and image.dtype == np.uint8
