"""The ``token_score_plain`` driver (a language model without a router) on
the family ``jamba`` (selective state-space layers among multi-query
attention layers), and the family's readers, on the CPU at tiny test-only
sizes (run by hand, like ``test_token_score_lfm2.py``):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_token_score_jamba.py -q -p no:cacheprovider

The cell is ``tiny_jamba.seq64`` of ``jamba_manifest.json``: 9 layers,
attention at 2 and 7 (Mamba runs of 2, 4 and 1), added as files. The driver
runs end to end through ``run.run``; the lower-precision control and each
planted fault fail a limit; each new reader is tried on a synthetic ``run``
and gives ``None``, never 0, with nothing to read.
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

from benchmark import flops_jamba, flops_lm, trace_reduce  # noqa: E402
from benchmark.reference import jamba as ref  # noqa: E402

CELL = "tiny_jamba.seq64"
TINY = dict(manifest_path=os.path.join(TESTS, "jamba_manifest.json"),
            workloads_dir=os.path.join(TESTS, "workloads"),
            device_check=False)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_READERS = ("score_mfu.jamba", "selective_scan_roofline.jamba")


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
    # no router: no margin rule, no clean share
    assert set(result["compared"]) == {"logit_gap_max", "logit_gap_rms",
                                       "rows_missing"}


def test_traced_run_reports_the_new_per_layer_metrics(bench, monkeypatch):
    # the CPU's trace has no device plane: hand the reduction the names a
    # chip trace of this program shows (PERF.md section 5)
    ms = 1_000_000
    events = [("%selective_scan.1 = bf16[2,64,128]{2,1,0} custom-call(...)",
               0, 30 * ms),
              ("%flash_attention_tiled.2 = f32[2,4,64,16]{3,2,1,0} "
               "custom-call(...)", 30 * ms, 5 * ms),
              ("%fusion.9 = f32[2,64]{1,0} fusion(...)", 40 * ms, 200 * ms)]
    monkeypatch.setattr(trace_reduce, "load_device_events",
                        lambda path: {"/device:TPU:0": events})
    monkeypatch.setattr(bench, "check_device", lambda chips: (
        bench.describe_device(), dict(PEAKS)))
    result = bench.run(["--workload", CELL, "--seed", "7", "--seconds", "1",
                        "--trace", "1"], **{**TINY, "device_check": True})
    metrics = result["metrics"]
    assert set(NEW_READERS) <= set(metrics), sorted(metrics)
    for name in NEW_READERS:
        assert 0 < metrics[name]["value"] < 100
    assert metrics["h2d_bytes_per_row.score"]["value"] == 4 * 64


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
    driver, ctx, _, window, program, reference = readings
    assert not fails(driver, ctx.workload, program, reference)
    assert window["window_tokens"] == 64 and "moe" not in window


def test_the_lower_precision_control_fails_a_limit(readings):
    driver, ctx, state, _, _, reference = readings
    control = driver.reference_readings(ctx, state, quant="float8_e4m3fn")
    assert fails(driver, ctx.workload, control, reference)


@pytest.mark.parametrize("fault", ["rows_shifted", "state_dropped"])
def test_each_planted_fault_fails_a_limit(readings, fault, monkeypatch):
    driver, ctx, state, _, _, reference = readings
    assert fault in driver.FAULTS
    # the tiny window is 64 positions: the fault drops the state every 16
    monkeypatch.setattr(ref, "DROP_EVERY", 16)
    broken = driver.reference_readings(ctx, state, fault=fault)
    assert fails(driver, ctx.workload, broken, reference)


def test_the_bfloat16_witness_is_finite_and_under_the_control(readings):
    driver, ctx, state, _, _, reference = readings
    witness = driver.compare(driver.reference_readings(
        ctx, state, quant="bfloat16"), reference)
    control = driver.compare(driver.reference_readings(
        ctx, state, quant="float8_e4m3fn"), reference)
    assert 0 < witness["logit_gap_rms"] < control["logit_gap_rms"]
    assert np.isfinite(witness["logit_gap_max"])


def test_an_answer_of_another_shape_or_not_finite_reads_infinite(readings):
    driver, _, _, _, program, reference = readings
    short = {"logprob": program["logprob"][:-1]}
    assert driver.compare(short, reference)["logit_gap_max"] == float("inf")
    bad = {"logprob": program["logprob"].copy()}
    bad["logprob"][0, 5] = np.nan
    assert driver.compare(bad, reference)["logit_gap_rms"] == float("inf")


# ---- the new readers on a synthetic run ----

def synthetic_run(bench, **over) -> dict:
    _, config, workload = cell_files(bench)
    run = {"config": config, "workload": workload, "peaks": dict(PEAKS),
           "chips": 1,
           "window": {"window_s": 2.0, "rows": 12, "calls": 2,
                      "window_tokens": 64},
           "trace": {"busy_s": 0.4, "window_s": 0.5, "device_ops": [
               ["%selective_scan.1 bf16[2,64,128]", 4e-6],
               ["%flash_attention_tiled.3 f32[2,4,64,16]", 1e-6],
               ["%fusion.1 f32[2,64]", 0.3]], "idle_gaps": []}}
    run.update(over)
    return run


def test_each_new_reader_reads_a_synthetic_run(bench):
    run = synthetic_run(bench)
    got = {name: bench.load_file_module("layer_metrics", name).read(run)
           for name in NEW_READERS}
    cfg = run["config"]
    per_row = flops_jamba.forward_flops(cfg, 64)["total"]
    assert got["score_mfu.jamba"] == pytest.approx(
        100 * per_row * 12 / 2.0 / 197e12)
    # the slice holds 12 / 2.0 * 0.5 / 6 = half a pass of 6 rows through 7
    # Mamba layers; the one instruction sums them all
    assert flops_lm.slice_passes(run) == pytest.approx(0.5)
    assert flops_jamba.count(cfg, "mamba") == 7
    least = 0.5 * 6 * 7 * max(
        flops_jamba.selective_scan_flops(cfg, 64) / 197e12,
        flops_jamba.selective_scan_bytes(cfg, 64) / 819e9)
    assert got["selective_scan_roofline.jamba"] == pytest.approx(
        100 * least / 4e-6)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_with_nothing_to_read_gives_none(bench, name):
    reader = bench.load_file_module("layer_metrics", name)
    # another family's run (the parent's program has no such module)
    other = synthetic_run(bench)
    other["config"] = dict(other["config"], family="lfm2")
    assert reader.read(other) is None
    bare = synthetic_run(bench)
    bare["window"] = {"window_s": 2.0, "rows": 0}
    assert reader.read(bare) is None
    if "roofline" in name:
        assert reader.read(synthetic_run(bench, trace=None)) is None
        # the kernel is not among the ten names
        gone = synthetic_run(bench)
        gone["trace"] = dict(gone["trace"],
                             device_ops=[["%fusion.1 f32[2,64]", 0.3]])
        assert reader.read(gone) is None
        # more kernels than the program has sites: a stack cut into runs
        many = synthetic_run(bench)
        many["trace"] = dict(many["trace"], device_ops=[
            [f"%selective_scan.{i} bf16[2,64,128]", 1e-6] for i in range(3)])
        assert reader.read(many) is None


def test_operation_counts_reproduce_the_issues_arithmetic():
    cfg = json.load(open(os.path.join(BENCH, "configs", "jamba2_3b.json")))
    part = flops_jamba.token_flops(cfg)
    assert part["mamba"] == 82_247_680                  # 82.3 M
    assert part["mlp"] == 6 * 2560 * 8192               # 125.8 M
    assert part["head"] == 2 * 2560 * 65536
    row = flops_jamba.forward_flops(cfg, 16384)
    assert row["total"] / 16384 == pytest.approx(6.22e9, rel=0.001)
    assert row["mamba"] / row["total"] == pytest.approx(0.34, abs=0.005)
    assert row["mlp"] / row["total"] == pytest.approx(0.57, abs=0.005)
    assert row["head"] / row["total"] == pytest.approx(0.054, abs=0.001)
    assert row["attention"] / row["total"] == pytest.approx(0.027, abs=0.001)
    assert flops_jamba.kinds(cfg).count("mamba") == 26
    assert [i for i, k in enumerate(flops_jamba.kinds(cfg))
            if k == "attention"] == [7, 21]
    # the kernel's own counts, a token a layer: 9 d_i N operations, 41.1 KB
    assert flops_jamba.selective_scan_flops(cfg, 1) == 9 * 5120 * 16
    assert flops_jamba.selective_scan_bytes(cfg, 1) == 41_088
    # by them the kernel is bound by bytes: 0.82 ms a layer-step of 16,384
    least = flops_jamba.selective_scan_bytes(cfg, 16384) / 819e9
    assert least == pytest.approx(0.822e-3, rel=0.001)
    assert flops_jamba.selective_scan_flops(cfg, 16384) / 197e12 < least


def test_the_configuration_keeps_every_published_key():
    cfg = json.load(open(os.path.join(BENCH, "configs", "jamba2_3b.json")))
    published = {
        "attn_layer_offset": 7, "attn_layer_period": 14,
        "expert_layer_offset": 1, "expert_layer_period": 2,
        "hidden_act": "silu", "hidden_size": 2560, "intermediate_size": 8192,
        "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 16,
        "mamba_dt_rank": 160, "mamba_expand": 2, "mamba_proj_bias": False,
        "max_position_embeddings": 262144, "model_type": "jamba",
        "num_attention_heads": 20, "num_experts": 1,
        "num_experts_per_tok": 1, "num_hidden_layers": 28,
        "num_key_value_heads": 1, "num_logits_to_keep": 1,
        "rms_norm_eps": 1e-06, "sliding_window": None,
        "tie_word_embeddings": True, "use_mamba_kernels": True,
        "vocab_size": 65536}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == [] and cfg["family"] == "jamba"
    assert cfg["parameters"] == 3_029_337_472
    assert cfg["parameter_bytes"] == 2 * cfg["parameters"]
    assert cfg["deployment"] and len(cfg["assumed"]) >= 6
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = "jamba2_3b_score.seq16k"
    assert [c for c in manifest["workloads"] if c["name"] == cell] == [
        {"name": cell, "config": "jamba2_3b", "traffic": "seq16k",
         "chips": 1, "why": manifest["workloads"][-1]["why"]}]
    reads = {m["name"] for m in manifest["per_layer"]
             if cell in m.get("workloads", [])}
    assert set(NEW_READERS) <= reads
    assert not any(name.startswith("moe_") for name in reads)
