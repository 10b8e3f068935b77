"""The ``token_score`` driver and the language-model readers, on the CPU at
tiny test-only sizes (run by hand, like ``test_harness.py``):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_token_score.py -q -p no:cacheprovider

The cell is ``tiny_mistral4.seq32`` of ``mistral4_manifest.json``: a
configuration, a workload and a manifest the harness had never seen, added
as files. The driver runs end to end through ``run.run``; the
lower-precision control and each planted fault fail a limit; each new
reader is tried on a synthetic ``run`` and gives ``None`` — never 0 — with
nothing to read.
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

from benchmark import flops_lm, trace_reduce  # noqa: E402

CELL = "tiny_mistral4.seq32"
TINY = dict(manifest_path=os.path.join(TESTS, "mistral4_manifest.json"),
            workloads_dir=os.path.join(TESTS, "workloads"),
            device_check=False)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_READERS = ("score_mfu.lm", "moe_grouped_matmul_roofline.score",
               "attention_core_roofline.score",
               "moe_held_pairs_per_token.score",
               "moe_expert_load_max_over_mean.score")


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
    assert {"device_idle_share.score", "h2d_bytes_per_row.score",
            "coerce_view_share.score"} <= set(metrics)
    # a quarter of the experts held, four picks a token: about one
    assert 0.5 < metrics["moe_held_pairs_per_token.score"]["value"] < 1.6
    assert metrics["moe_expert_load_max_over_mean.score"]["value"] >= 1.0
    for name in ("score_mfu.lm", "moe_grouped_matmul_roofline.score",
                 "attention_core_roofline.score"):
        assert 0 < metrics[name]["value"] < 100
    # an int32 token column ships as float32: 4 bytes an id, copied
    assert metrics["h2d_bytes_per_row.score"]["value"] == 4 * 32
    assert metrics["coerce_view_share.score"]["value"] == 0.0


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


def test_a_short_clean_share_and_a_lost_row_fail(readings):
    driver, ctx, _, _, program, reference = readings
    strict = dict(reference, clean_margin=1.0)        # no token is clean
    assert driver.compare(program, strict)["clean_share_short"] > 0
    fewer = {"logprob": program["logprob"][:-1]}
    assert driver.compare(fewer, reference)["logit_gap_max"] == float("inf")


def test_clean_tokens_follow_the_margin_of_the_position_before():
    driver = importlib.import_module("benchmark.drivers.token_score")
    margin = np.array([[0.5, 0.0, 0.5, 0.5]])
    np.testing.assert_array_equal(driver.clean_tokens(margin, 0.1),
                                  [[False, True, False, True]])


def test_the_window_carries_the_load_counts(readings):
    _, ctx, _, window, _, _ = readings
    moe = window["moe"]
    layers, held = ctx.config["num_hidden_layers"], 4
    assert np.asarray(moe["load"]).shape == (layers, held)
    assert moe["moe.held_pairs"] == int(np.sum(moe["load"]))
    assert moe["moe.tokens"] == 12 * 32 * layers
    assert moe["moe.expert_load_max"] == int(np.max(moe["load"]))
    from mmlspark_tpu.obs.metrics import registry
    assert registry().value("moe.held_pairs") >= moe["moe.held_pairs"]


# ---- the new readers on a synthetic run ----

def synthetic_run(bench, **over) -> dict:
    _, config, workload = cell_files(bench)
    load = [[100, 90, 110, 100]] * config["num_hidden_layers"]
    run = {"config": config, "workload": workload, "peaks": dict(PEAKS),
           "chips": 1,
           "window": {"window_s": 2.0, "rows": 24, "calls": 2,
                      "window_tokens": 32,
                      "moe": {"load": load, "moe.tokens": 12 * 32 * 3,
                              "moe.held_pairs": 1200,
                              "moe.expert_load_max": 110}},
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
    assert got["moe_held_pairs_per_token.score"] == pytest.approx(
        1200 / 1152)
    assert got["moe_expert_load_max_over_mean.score"] == pytest.approx(1.1)
    cfg = run["config"]
    per_row = flops_lm.forward_flops(cfg, 32, 1200 / 1152)["total"]
    assert got["score_mfu.lm"] == pytest.approx(
        100 * per_row * 24 / 2.0 / 197e12)
    # the slice holds 24 / 2.0 * 0.5 / 12 = half a pass
    assert flops_lm.slice_passes(run) == pytest.approx(0.5)
    work = flops_lm.grouped_product_work(cfg, 1200, 3 * 12 / 4)
    least = 0.5 * sum(max(ops / 197e12, nbytes / 819e9) for ops, nbytes in
                      (work["gate"], work["gate"], work["down"]))
    assert got["moe_grouped_matmul_roofline.score"] == pytest.approx(
        100 * least / 7e-6)
    core = 0.5 * 12 * 3 * max(
        flops_lm.attention_core_flops(cfg, 32) / 197e12,
        flops_lm.attention_core_bytes(cfg, 32) / 819e9)
    assert got["attention_core_roofline.score"] == pytest.approx(
        100 * core / 1e-6)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_with_nothing_to_read_gives_none(bench, name):
    reader = bench.load_file_module("layer_metrics", name)
    bare = synthetic_run(bench)
    bare["window"] = {"window_s": 2.0, "rows": 24, "window_tokens": 32}
    bare["trace"] = {"busy_s": 0.4, "window_s": 0.5, "idle_gaps": [],
                     "device_ops": [["%fusion.1 f32[4,32]", 0.3]]}
    assert reader.read(bare) is None
    untraced = synthetic_run(bench, trace=None)
    if "roofline" in name:
        assert reader.read(untraced) is None


def test_operation_counts_reproduce_the_issues_arithmetic():
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "mistral_small4_ep4.json")))
    part = flops_lm.token_layer_flops(cfg)
    assert part["projections"] == pytest.approx(56.1e6, rel=0.01)
    assert part["shared"] == part["routed"] == 6 * 4096 * 2048
    assert part["router"] == 2 * 4096 * 128
    row = flops_lm.forward_flops(cfg, 4096, 1.0)
    assert row["total"] / 4096 == pytest.approx(1.42e9, rel=0.01)
    assert row["total"] == pytest.approx(5.8e12, rel=0.01)
    assert row["head"] / row["total"] == pytest.approx(0.19, abs=0.005)
    assert (flops_lm.attention_core_flops(cfg, 4096) / 4096
            == pytest.approx(33.6e6, rel=0.01))
    # a share past 100 % would say operations or bytes are counted too high
    assert flops_lm.roofline_percent([(197e12, 0)], 1.0, PEAKS) == 100.0
    assert flops_lm.roofline_percent([], 1.0, PEAKS) is None


def test_the_configuration_keeps_every_published_width():
    cfg = json.load(open(os.path.join(BENCH, "configs",
                                      "mistral_small4_ep4.json")))
    published = {"hidden_size": 4096, "q_lora_rank": 1024,
                 "kv_lora_rank": 256, "qk_nope_head_dim": 64,
                 "qk_rope_head_dim": 64, "v_head_dim": 128,
                 "moe_intermediate_size": 2048, "num_attention_heads": 32,
                 "num_experts_per_tok": 4, "router_width": 128,
                 "intermediate_size": 12288, "n_shared_experts": 1}
    assert {k: cfg[k] for k in published} == published
    assert cfg["reduced"] == ["num_hidden_layers", "n_routed_experts",
                              "vocab_size"]
    assert cfg["published"] == {"num_hidden_layers": 36,
                                "n_routed_experts": 128,
                                "vocab_size": 131072}
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (6, 32, 32768)
    assert cfg["deployment"] and len(cfg["assumed"]) >= 4
