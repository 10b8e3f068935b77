"""The ``token_score_hybrid`` driver (``token_score`` with the lost-state
fault beside its own) on the family ``nemotron_h`` (Mamba-2 layers, ungated
squared-ReLU experts behind a sigmoid router with a selection bias,
grouped-query attention, an untied head), and the family's readers, on the
CPU at tiny test-only sizes (run by hand, like ``test_token_score_jamba.py``):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_token_score_nemotron_h.py -q -p no:cacheprovider

The cell is ``tiny_nemotron_h.seq300`` of ``nemotron_h_manifest.json``: the
pattern ``MEM*E``, 4 of the router's 8 experts held, windows of 300
positions (two whole chunks of the scan and a tail), added as files. The
driver runs end to end through ``run.run``; the lower-precision control and
each planted fault fail a limit; each new reader is tried on a synthetic
``run`` and gives ``None``, never 0, with nothing to read.
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

from benchmark import flops_lm, flops_nemotron_h, trace_reduce  # noqa: E402

CELL = "tiny_nemotron_h.seq300"
TINY = dict(manifest_path=os.path.join(TESTS, "nemotron_h_manifest.json"),
            workloads_dir=os.path.join(TESTS, "workloads"),
            device_check=False)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_READERS = ("score_mfu.nemotron_h", "ssd_scan_roofline.nemotron_h",
               "moe_grouped_matmul_roofline.nemotron_h",
               "attention_core_roofline.nemotron_h")


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
    events = [("%ssd_scan.1 = bf16[2,384,64]{2,1,0} custom-call(...)",
               0, 20 * ms),
              ("%flash_attention_tiled.2 = f32[2,4,300,16]{3,2,1,0} "
               "custom-call(...)", 20 * ms, 10 * ms),
              ("%gmm.12 = f32[768,48]{1,0} custom-call(...)", 30 * ms,
               10 * ms),
              ("%gmm.13 = bf16[768,64]{1,0} custom-call(...)", 40 * ms,
               10 * ms),
              ("%fusion.9 = f32[2,300]{1,0} fusion(...)", 60 * ms, 200 * ms)]
    monkeypatch.setattr(trace_reduce, "load_device_events",
                        lambda path: {"/device:TPU:0": events})
    monkeypatch.setattr(bench, "check_device", lambda chips: (
        bench.describe_device(), dict(PEAKS)))
    result = bench.run(["--workload", CELL, "--seed", "7", "--seconds", "1",
                        "--trace", "1"], **{**TINY, "device_check": True})
    metrics = result["metrics"]
    assert set(NEW_READERS) <= set(metrics), sorted(metrics)
    # half the router's experts are held: about one of a token's two picks
    assert 0.5 < metrics["moe_held_pairs_per_token.score"]["value"] < 1.5
    assert metrics["moe_expert_load_max_over_mean.score"]["value"] >= 1.0
    for name in NEW_READERS:
        assert 0 < metrics[name]["value"] < 100
    assert metrics["h2d_bytes_per_row.score"]["value"] == 4 * 300


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
    assert window["window_tokens"] == 300


def test_the_lower_precision_control_fails_a_limit(readings):
    driver, ctx, state, _, _, reference = readings
    control = driver.reference_readings(ctx, state, quant="float8_e4m3fn")
    assert fails(driver, ctx.workload, control, reference)


@pytest.mark.parametrize("fault", ["rows_shifted", "expert_swapped",
                                   "state_dropped"])
def test_each_planted_fault_fails_a_limit(readings, fault):
    driver, ctx, state, _, _, reference = readings
    assert fault in driver.FAULTS
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


def test_the_window_carries_the_load_counts_of_the_expert_layers(readings):
    _, ctx, _, window, _, _ = readings
    moe = window["moe"]
    expert_layers = ctx.config["hybrid_override_pattern"].count("E")
    # the driver counts the expert layers from the node's width: held 4
    assert np.asarray(moe["load"]).shape == (expert_layers, 4)
    assert moe["moe.tokens"] == 6 * 300 * expert_layers
    assert 0 < moe["moe.held_pairs"] < 2 * moe["moe.tokens"]


# ---- the new readers on a synthetic run ----

def synthetic_run(bench, **over) -> dict:
    _, config, workload = cell_files(bench)
    run = {"config": config, "workload": workload, "peaks": dict(PEAKS),
           "chips": 1,
           "window": {"window_s": 2.0, "rows": 12, "calls": 2,
                      "window_tokens": 300,
                      "moe": {"load": [[450] * 4] * 2,
                              "moe.tokens": 6 * 300 * 2,
                              "moe.held_pairs": 6 * 300 * 2,
                              "moe.expert_load_max": 450}},
           "trace": {"busy_s": 0.4, "window_s": 0.5, "device_ops": [
               ["%ssd_scan.1 bf16[2,384,64]", 4e-6],
               ["%flash_attention_tiled.3 f32[2,4,300,16]", 1e-6],
               ["%gmm.1 f32[768,48]", 2e-6], ["%gmm.2 bf16[768,64]", 3e-6],
               ["%fusion.1 f32[2,300]", 0.3]], "idle_gaps": []}}
    run.update(over)
    return run


def test_each_new_reader_reads_a_synthetic_run(bench):
    run = synthetic_run(bench)
    got = {name: bench.load_file_module("layer_metrics", name).read(run)
           for name in NEW_READERS}
    cfg = run["config"]
    per_row = flops_nemotron_h.forward_flops(cfg, 300, 1.0)["total"]
    assert got["score_mfu.nemotron_h"] == pytest.approx(
        100 * per_row * 12 / 2.0 / 197e12)
    # the slice holds 12 / 2.0 * 0.5 / 6 = half a pass of 6 rows through 2
    # Mamba-2, 2 expert and 1 attention layers; one instruction a kernel
    # sums them all
    assert flops_lm.slice_passes(run) == pytest.approx(0.5)
    assert [flops_nemotron_h.count(cfg, k) for k in
            ("mamba2", "moe", "attention")] == [2, 2, 1]
    least = 0.5 * 6 * 2 * max(
        flops_nemotron_h.ssd_scan_flops(cfg, 300) / 197e12,
        flops_nemotron_h.ssd_scan_bytes(cfg, 300) / 819e9)
    assert got["ssd_scan_roofline.nemotron_h"] == pytest.approx(
        100 * least / 4e-6)
    work = flops_nemotron_h.grouped_product_work(cfg, 6 * 300 * 2,
                                                 2 * 6 / 2)
    least = 0.5 * sum(max(ops / 197e12, nbytes / 819e9)
                      for ops, nbytes in work.values())
    assert got["moe_grouped_matmul_roofline.nemotron_h"] == pytest.approx(
        100 * least / 5e-6)
    core = 0.5 * 6 * 1 * max(
        flops_nemotron_h.attention_core_flops(cfg, 300) / 197e12,
        flops_nemotron_h.attention_core_bytes(cfg, 300) / 819e9)
    assert got["attention_core_roofline.nemotron_h"] == pytest.approx(
        100 * core / 1e-6)


def test_a_second_rungs_products_share_the_same_work(bench):
    """Steps that took the ladder's second rung show as two more grouped
    products: the work is counted once a product, the seconds all summed."""
    reader = bench.load_file_module("layer_metrics",
                                    "moe_grouped_matmul_roofline.nemotron_h")
    one = reader.read(synthetic_run(bench))
    run = synthetic_run(bench)
    run["trace"] = dict(run["trace"], device_ops=run["trace"]["device_ops"]
                        + [["%gmm.5 f32[768,48]", 2e-6],
                           ["%gmm.6 bf16[768,64]", 3e-6]])
    assert reader.read(run) == pytest.approx(one / 2)
    # only the up product among the ten names: its work over its seconds
    run["trace"] = dict(run["trace"],
                        device_ops=[["%gmm.1 f32[768,48]", 2e-6]])
    cfg = run["config"]
    ops, nbytes = flops_nemotron_h.grouped_product_work(
        cfg, 6 * 300 * 2, 6)["up"]
    assert reader.read(run) == pytest.approx(
        100 * 0.5 * max(ops / 197e12, nbytes / 819e9) / 2e-6)


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
                             device_ops=[["%fusion.1 f32[2,300]", 0.3]])
        assert reader.read(gone) is None
        # more kernels than the program has sites: a stack cut into runs
        many = synthetic_run(bench)
        many["trace"] = dict(many["trace"], device_ops=[
            [f"%ssd_scan.{i} bf16[2,384,64]", 1e-6] for i in range(3)] + [
            [f"%flash_attention_tiled.{i} f32[2,4,300,16]", 1e-6]
            for i in range(2)] + [[f"%gmm.{i} f32[768,48]", 1e-6]
                                  for i in range(5)])
        assert reader.read(many) is None


def test_operation_counts_reproduce_the_issues_arithmetic():
    cfg = json.load(open(os.path.join(
        BENCH, "configs", "nemotron3_nano_ep2_stage13.json")))
    part = flops_nemotron_h.token_flops(cfg)
    # in and out products 77.4 M a layer, the recurrence's 3.4 M
    assert part["mamba2"] - flops_nemotron_h.ssd_scan_flops(cfg, 1) \
        == 2 * (2688 * 10304 + 4096 * 2688)
    assert flops_nemotron_h.ssd_scan_flops(cfg, 1) == 3_407_872
    assert part["routed"] == 4 * 2688 * 1856            # two products
    assert part["shared"] == 4 * 2688 * 3712
    assert part["head"] == 2 * 2688 * 65536
    row = flops_nemotron_h.forward_flops(cfg, 16384, 3.0)
    assert row["total"] / 16384 == pytest.approx(1.702e9, rel=0.001)
    assert row["total"] == pytest.approx(27.9e12, rel=0.002)
    assert row["mamba2"] / row["total"] == pytest.approx(0.285, abs=0.002)
    experts = row["router"] + row["routed"] + row["shared"]
    assert experts / row["total"] == pytest.approx(0.295, abs=0.002)
    attention = row["attention"] + row["attention_projections"]
    assert attention / row["total"] == pytest.approx(0.213, abs=0.002)
    assert row["head"] / row["total"] == pytest.approx(0.207, abs=0.002)
    assert [flops_nemotron_h.count(cfg, k) for k in
            ("mamba2", "moe", "attention")] == [6, 5, 2]
    # the kernel's own counts, a token a layer: 20.7 KB; by them the kernel
    # is bound by bytes: 0.42 ms a layer-step of 16,384 against 0.28 ms
    assert flops_nemotron_h.ssd_scan_bytes(cfg, 1) == 20_736
    least = flops_nemotron_h.ssd_scan_bytes(cfg, 16384) / 819e9
    assert least == pytest.approx(0.415e-3, rel=0.001)
    assert flops_nemotron_h.ssd_scan_flops(cfg, 16384) / 197e12 < least
    # keys and values move once a KV head: 2, not 32
    assert flops_nemotron_h.attention_core_bytes(cfg, 16384) \
        == 16384 * 128 * (2 * 32 + 4 * 2 + 4 * 32)


def test_the_configuration_keeps_every_published_key():
    cfg = json.load(open(os.path.join(
        BENCH, "configs", "nemotron3_nano_ep2_stage13.json")))
    rows = [json.loads(line) for line in open(
        "/opt/skills/guides/model-configs/architectures.jsonl")] \
        if os.path.exists("/opt/skills/guides/model-configs/"
                          "architectures.jsonl") else []
    row = next((r for r in rows if r["name"]
                == "NVIDIA-Nemotron-3-Nano-30B-A3B-BF16"), None)
    if row is None:
        pytest.skip("the catalog is not on this machine")
    reduced = ["num_hidden_layers", "hybrid_override_pattern",
               "n_routed_experts", "vocab_size"]
    assert cfg["reduced"] == reduced and cfg["family"] == "nemotron_h"
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in reduced:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["n_routed_experts"],
            cfg["vocab_size"]) == (13, 64, 65536)
    assert cfg["hybrid_override_pattern"] == row["config"][
        "hybrid_override_pattern"][:13] == "MEMEM*EMEMEM*"
    assert (cfg["router_width"], cfg["first_expert"]) == (128, 0)
    assert cfg["parameters"] == 3_926_018_560
    assert cfg["parameter_bytes"] == 2 * cfg["parameters"]
    assert cfg["deployment"] and len(cfg["assumed"]) >= 8
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = "nemotron3_nano_score.seq16k"
    assert [c for c in manifest["workloads"] if c["name"] == cell] == [
        {"name": cell, "config": "nemotron3_nano_ep2_stage13",
         "traffic": "seq16k", "chips": 1,
         "why": manifest["workloads"][-1]["why"]}]
    assert len(manifest["workloads"]) == 7
    assert sum(c["chips"] == 4 for c in manifest["workloads"]) == 1
    reads = {m["name"] for m in manifest["per_layer"]
             if cell in m.get("workloads", [])}
    # the scan kernel's reader is in the tree and is NOT listed: at 1.4 ms a
    # layer-step the kernel is 3 % of a step and lies under the ten names a
    # traced slice keeps (PERF.md section 7), so it would find nothing
    assert set(NEW_READERS) - reads == {"ssd_scan_roofline.nemotron_h"}
    assert {"moe_held_pairs_per_token.score",
            "moe_expert_load_max_over_mean.score",
            "compiles_in_window.score"} <= reads
    assert not any(name.endswith((".lfm2", ".jamba", ".lm"))
                   for name in reads)
