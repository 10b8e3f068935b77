"""The ``token_score`` driver, used as it is, on the family ``lfm2`` (conv
and grouped-query layers, a sigmoid router with a selection bias, a tied
head), and the family's readers, on the CPU at tiny test-only sizes (run by
hand, like ``test_token_score.py``):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_token_score_lfm2.py -q -p no:cacheprovider

The cell is ``tiny_lfm2.seq32`` of ``lfm2_manifest.json``: 9 layers (one
leading dense conv layer and two periods), added as files. The driver runs
end to end through ``run.run``; the lower-precision control and each planted
fault fail a limit; each new reader is tried on a synthetic ``run`` and
gives ``None``, never 0, with nothing to read.
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

from benchmark import flops_lfm2, flops_lm, trace_reduce  # noqa: E402

CELL = "tiny_lfm2.seq32"
TINY = dict(manifest_path=os.path.join(TESTS, "lfm2_manifest.json"),
            workloads_dir=os.path.join(TESTS, "workloads"),
            device_check=False)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_READERS = ("score_mfu.lfm2", "moe_grouped_matmul_roofline.lfm2",
               "attention_core_roofline.lfm2")


@pytest.fixture(scope="module")
def bench():
    spec = importlib.util.spec_from_file_location(
        "benchmark_run", os.path.join(BENCH, "run.py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def cell_files(bench):
    _, entry, config, workload = bench.load_cell(
        CELL, TINY["manifest_path"], TINY["workloads_dir"])
    return entry, config, workload


# ---- the driver, end to end ----

def test_untraced_run_is_correct_and_reports_rows_per_second(bench):
    result = json.loads(json.dumps(bench.run(
        ["--workload", CELL, "--seed", "3000000019", "--seconds", "1",
         "--trace", "0"], **TINY)))
    assert result["correct"] is True, result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert set(result["metrics"]) == {"score_rows_per_s", "setup_s"}
    assert set(result["compared"]) == {"logit_gap_max", "logit_gap_rms",
                                       "clean_share_short", "rows_missing"}


def test_traced_run_reports_the_new_per_layer_metrics(bench, monkeypatch):
    # the CPU's trace has no device plane: hand the reduction the names a
    # chip trace of this program shows (PERF.md section 5)
    ms = 1_000_000
    events = [("%flash_attention_tiled.12 = f32[4,4,32,16]{3,2,1,0} "
               "custom-call(...)", 0, 20 * ms),
              ("%gmm.12 = f32[512,32]{1,0} custom-call(...)", 20 * ms,
               10 * ms),
              ("%gmm.13 = f32[512,32]{1,0} custom-call(...)", 30 * ms,
               10 * ms),
              ("%gmm.14 = bf16[512,64]{1,0} custom-call(...)", 40 * ms,
               10 * ms),
              ("%fusion.9 = f32[4,32]{1,0} fusion(...)", 60 * ms, 200 * ms)]
    monkeypatch.setattr(trace_reduce, "load_device_events",
                        lambda path: {"/device:TPU:0": events})
    monkeypatch.setattr(bench, "check_device", lambda chips: (
        bench.describe_device(), dict(PEAKS)))
    result = bench.run(["--workload", CELL, "--seed", "7", "--seconds", "1",
                        "--trace", "1"], **{**TINY, "device_check": True})
    metrics = result["metrics"]
    assert set(NEW_READERS) <= set(metrics), sorted(metrics)
    # every expert is held: four picks a token in every expert layer
    assert metrics["moe_held_pairs_per_token.score"]["value"] == 4.0
    assert metrics["moe_expert_load_max_over_mean.score"]["value"] >= 1.0
    for name in NEW_READERS:
        assert 0 < metrics[name]["value"] < 100
    assert metrics["h2d_bytes_per_row.score"]["value"] == 4 * 32


# ---- the control and the planted faults ----

@pytest.fixture(scope="module")
def readings(bench):
    """One driven window and its reference readings."""
    entry, config, workload = cell_files(bench)
    driver = bench.load_file_module("drivers", workload["driver"])
    ctx = bench.Context(entry, config, workload, None, 2147483659, 0.3, False)
    state = driver.setup(ctx)
    window = driver.measure(ctx, state)
    program = driver.program_readings(state)
    driver.release(state)
    return (driver, ctx, state, window, program,
            driver.reference_readings(ctx, state))


def fails(driver, workload, broken, reference) -> bool:
    numbers = driver.compare(broken, reference)
    return any(numbers[k] > limit for k, limit in workload["limits"].items()
               if k in numbers)


def test_the_program_itself_passes(readings):
    driver, ctx, _, _, program, reference = readings
    assert not fails(driver, ctx.workload, program, reference)


def test_the_lower_precision_control_fails_a_limit(readings):
    driver, ctx, state, _, _, reference = readings
    control = driver.reference_readings(ctx, state, quant="float8_e4m3fn")
    assert fails(driver, ctx.workload, control, reference)


@pytest.mark.parametrize("fault", ["rows_shifted", "expert_swapped"])
def test_each_planted_fault_fails_a_limit(readings, fault):
    driver, ctx, state, _, _, reference = readings
    assert fault in driver.FAULTS
    broken = driver.reference_readings(ctx, state, fault=fault)
    assert fails(driver, ctx.workload, broken, reference)


def test_the_bfloat16_witness_is_finite_and_under_the_control(readings):
    """``quant="bfloat16"`` (operands and residual stream in the
    configuration's own type) reads a gap, not ``inf``, and a smaller one
    than the e4m3 control."""
    driver, ctx, state, _, _, reference = readings
    witness = driver.compare(driver.reference_readings(
        ctx, state, quant="bfloat16"), reference)
    control = driver.compare(driver.reference_readings(
        ctx, state, quant="float8_e4m3fn"), reference)
    assert 0 < witness["logit_gap_rms"] < control["logit_gap_rms"]
    assert np.isfinite(witness["logit_gap_max"])


def test_the_window_carries_the_load_counts_of_the_expert_layers(readings):
    _, ctx, _, window, _, _ = readings
    moe = window["moe"]
    expert_layers = (ctx.config["num_hidden_layers"]
                     - ctx.config["num_dense_layers"])
    assert np.asarray(moe["load"]).shape == (expert_layers, 8)
    assert moe["moe.tokens"] == 12 * 32 * expert_layers
    assert moe["moe.held_pairs"] == 4 * moe["moe.tokens"]


# ---- the new readers on a synthetic run ----

def synthetic_run(bench, **over) -> dict:
    _, config, workload = cell_files(bench)
    layers = config["num_hidden_layers"] - config["num_dense_layers"]
    run = {"config": config, "workload": workload, "peaks": dict(PEAKS),
           "chips": 1,
           "window": {"window_s": 2.0, "rows": 24, "calls": 2,
                      "window_tokens": 32,
                      "moe": {"load": [[192] * 8] * layers,
                              "moe.tokens": 12 * 32 * layers,
                              "moe.held_pairs": 4 * 12 * 32 * layers,
                              "moe.expert_load_max": 192}},
           "trace": {"busy_s": 0.4, "window_s": 0.5, "device_ops": [
               ["%flash_attention_tiled.3 f32[4,4,32,16]", 1e-6],
               ["%gmm.1 f32[512,32]", 2e-6], ["%gmm.2 f32[512,32]", 2e-6],
               ["%ragged-dot-none.2 bf16[512,64]", 3e-6],
               ["%fusion.1 f32[4,32]", 0.3]], "idle_gaps": []}}
    run.update(over)
    return run


def test_each_new_reader_reads_a_synthetic_run(bench):
    run = synthetic_run(bench)
    got = {name: bench.load_file_module("layer_metrics", name).read(run)
           for name in NEW_READERS}
    cfg = run["config"]
    per_row = flops_lfm2.forward_flops(cfg, 32, 4.0)["total"]
    assert got["score_mfu.lfm2"] == pytest.approx(
        100 * per_row * 24 / 2.0 / 197e12)
    # the slice holds 24 / 2.0 * 0.5 / 12 = half a pass; the tiny stack is
    # two periods of four layers, each with four expert layers and one
    # attention layer, so a found operation did a quarter of its product's
    # work and the one attention operation all of its kind's
    assert flops_lm.slice_passes(run) == pytest.approx(0.5)
    assert flops_lfm2.a_period(cfg, "moe") == 4
    assert flops_lfm2.a_period(cfg, "full_attention") == 1
    pairs = run["window"]["moe"]["moe.held_pairs"]
    work = flops_lfm2.grouped_product_work(cfg, pairs, 8 * 12 / 4)
    least = 0.5 / 4 * sum(max(ops / 197e12, nbytes / 819e9)
                          for ops, nbytes in (work["gate"], work["gate"],
                                              work["down"]))
    assert got["moe_grouped_matmul_roofline.lfm2"] == pytest.approx(
        100 * least / 7e-6)
    core = 0.5 * 12 * 2 * max(
        flops_lfm2.attention_core_flops(cfg, 32) / 197e12,
        flops_lfm2.attention_core_bytes(cfg, 32) / 819e9)
    assert got["attention_core_roofline.lfm2"] == pytest.approx(
        100 * core / 1e-6)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_with_nothing_to_read_gives_none(bench, name):
    reader = bench.load_file_module("layer_metrics", name)
    bare = synthetic_run(bench)
    bare["window"] = {"window_s": 2.0, "rows": 24, "window_tokens": 32}
    bare["trace"] = {"busy_s": 0.4, "window_s": 0.5, "idle_gaps": [],
                     "device_ops": [["%fusion.1 f32[4,32]", 0.3]]}
    assert reader.read(bare) is None
    # another family's run (the parent's program has no such module)
    other = synthetic_run(bench)
    other["config"] = dict(other["config"], family="mistral4")
    assert reader.read(other) is None
    if "roofline" in name:
        assert reader.read(synthetic_run(bench, trace=None)) is None
        # a layer list that is no single period gives nothing to divide by
        broken = synthetic_run(bench)
        broken["config"] = dict(broken["config"], layer_types=(
            broken["config"]["layer_types"][:-1] + ["full_attention"]))
        assert reader.read(broken) is None
        # more kernels than a period has: the scan is cut otherwise
        many = synthetic_run(bench)
        many["trace"] = dict(many["trace"], device_ops=[
            [f"%flash_attention_tiled.{i} f32[4,4,32,16]", 1e-6]
            for i in range(2)] + [[f"%gmm.{i} f32[512,32]", 1e-6]
                                  for i in range(13)])
        assert reader.read(many) is None


def test_operation_counts_reproduce_the_issues_arithmetic():
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "lfm2_8b_a1b_stage13.json")))
    part = flops_lfm2.token_flops(cfg)
    assert part["conv"] == pytest.approx(33.55e6, rel=0.001)
    assert part["dense"] == 4 * part["routed"] == 6 * 2048 * 7168
    row = flops_lfm2.forward_flops(cfg, 8192, 4.0)
    assert row["total"] / 8192 == pytest.approx(1.914e9, rel=0.001)
    assert row["total"] == pytest.approx(15.68e12, rel=0.001)
    assert row["routed"] / row["total"] == pytest.approx(0.55, abs=0.005)
    assert row["head"] / row["total"] == pytest.approx(0.14, abs=0.005)
    assert ((row["attention"] + row["attention_projections"]) / 8192
            == pytest.approx(163.6e6, rel=0.001))
    # keys and values move once a KV head: 8, not 32
    assert flops_lfm2.attention_core_bytes(cfg, 8192) == 8192 * 64 * (
        2 * 32 + 4 * 8 + 4 * 32)


def test_the_configuration_keeps_every_published_width():
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "lfm2_8b_a1b_stage13.json")))
    published = {"hidden_size": 2048, "intermediate_size": 7168,
                 "moe_intermediate_size": 1792, "num_attention_heads": 32,
                 "num_key_value_heads": 8, "num_experts": 32,
                 "num_experts_per_tok": 4, "conv_L_cache": 3,
                 "vocab_size": 65536, "rope_theta": 1000000,
                 "norm_eps": 1e-5}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers", "num_dense_layers",
                              "layer_types"]
    assert cfg["published"]["num_hidden_layers"] == 24
    assert cfg["published"]["num_dense_layers"] == 2
    assert len(cfg["published"]["layer_types"]) == 24
    assert cfg["layer_types"] == ["conv"] + cfg["published"]["layer_types"][
        2:14] == ["conv"] + ["full_attention", "conv", "conv", "conv"] * 3
    assert cfg["deployment"] and len(cfg["assumed"]) >= 5
