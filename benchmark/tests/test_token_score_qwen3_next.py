"""The ``token_score_gdn`` driver (``token_score`` with the lost-state fault
beside its own, stopping at once on a program without the family) on the
family ``qwen3_next`` (Gated DeltaNet layers, gated attention, a softmax
router over gated experts and a gated shared expert, an untied head), and the
family's readers, on the CPU at tiny test-only sizes (run by hand, like
``test_token_score_nemotron_h.py``):

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests/test_token_score_qwen3_next.py -q -p no:cacheprovider

The cell is ``tiny_qwen3_next.seq300`` of ``qwen3_next_manifest.json``: 8
layers (two periods of three Gated DeltaNet layers and one of attention), 4
of the router's 16 experts held, windows of 300 positions (four chunks of the
recurrence and a tail), added as files. The driver runs end to end through
``run.run``; the lower-precision control and each planted fault fail a limit;
each new reader is tried on a synthetic ``run`` and gives ``None``, never 0,
with nothing to read.
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

from benchmark import flops_lm, flops_qwen3_next, trace_reduce  # noqa: E402

CELL = "tiny_qwen3_next.seq300"
TINY = dict(manifest_path=os.path.join(TESTS, "qwen3_next_manifest.json"),
            workloads_dir=os.path.join(TESTS, "workloads"),
            device_check=False)
PEAKS = {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
NEW_READERS = ("score_mfu.qwen3_next", "gated_delta_roofline.qwen3_next",
               "moe_grouped_matmul_roofline.qwen3_next",
               "attention_core_roofline.qwen3_next")


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
    # chip trace of this program shows
    ms = 1_000_000
    events = [("%gated_delta.1 = bf16[2,300,128]{2,1,0} custom-call(...)",
               0, 20 * ms),
              ("%flash_attention_tiled.2 = f32[2,4,300,32]{3,2,1,0} "
               "custom-call(...)", 20 * ms, 10 * ms),
              ("%gmm.12 = f32[768,32]{1,0} custom-call(...)", 30 * ms,
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
    # a quarter of the router's experts held: about one of a token's four
    assert 0.5 < metrics["moe_held_pairs_per_token.score"]["value"] < 1.5
    assert metrics["moe_expert_load_max_over_mean.score"]["value"] >= 1.0
    for name in NEW_READERS:
        assert 0 < metrics[name]["value"] < 100
    assert metrics["h2d_bytes_per_row.score"]["value"] == 4 * 300


def test_a_program_without_the_family_stops_at_once(bench, monkeypatch):
    """The parent has no ``models/lm_gdn.py``: set-up stops on its import
    (the cell is then measured on the change alone)."""
    entry, config, workload = cell_files(bench)
    driver = bench.load_file_module("drivers", workload["driver"])
    import mmlspark_tpu.models as models
    monkeypatch.delattr(models, "lm_gdn", raising=False)
    monkeypatch.setitem(sys.modules, "mmlspark_tpu.models.lm_gdn", None)
    ctx = bench.Context(entry, config, workload, None, 1, 0.3, False)
    with pytest.raises(ImportError):
        driver.setup(ctx)


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


def test_the_window_carries_the_load_counts_of_the_expert_layers(readings):
    _, ctx, _, window, _, _ = readings
    moe = window["moe"]
    layers = ctx.config["num_hidden_layers"]
    # every layer has its experts; the driver reads the node's width: held 4
    assert np.asarray(moe["load"]).shape == (layers, 4)
    assert moe["moe.tokens"] == 6 * 300 * layers
    assert 0 < moe["moe.held_pairs"] < 4 * moe["moe.tokens"]


# ---- the new readers on a synthetic run ----

def synthetic_run(bench, **over) -> dict:
    _, config, workload = cell_files(bench)
    run = {"config": config, "workload": workload, "peaks": dict(PEAKS),
           "chips": 1,
           "window": {"window_s": 2.0, "rows": 12, "calls": 2,
                      "window_tokens": 300,
                      "moe": {"load": [[450] * 4] * 8,
                              "moe.tokens": 6 * 300 * 8,
                              "moe.held_pairs": 6 * 300 * 8,
                              "moe.expert_load_max": 450}},
           "trace": {"busy_s": 0.4, "window_s": 0.5, "device_ops": [
               ["%gated_delta.1 bf16[2,384,128]", 4e-6],
               ["%flash_attention_tiled.3 f32[2,4,300,32]", 1e-6],
               ["%gmm.1 f32[768,32]", 2e-6], ["%gmm.2 f32[768,32]", 2e-6],
               ["%gmm.3 bf16[768,64]", 3e-6],
               ["%fusion.1 f32[2,300]", 0.3]], "idle_gaps": []}}
    run.update(over)
    return run


def test_each_new_reader_reads_a_synthetic_run(bench):
    run = synthetic_run(bench)
    got = {name: bench.load_file_module("layer_metrics", name).read(run)
           for name in NEW_READERS}
    cfg = run["config"]
    per_row = flops_qwen3_next.forward_flops(cfg, 300, 1.0)["total"]
    assert got["score_mfu.qwen3_next"] == pytest.approx(
        100 * per_row * 12 / 2.0 / 197e12)
    # the slice holds 12 / 2.0 * 0.5 / 6 = half a pass of 6 rows through 6
    # Gated DeltaNet, 2 attention and 8 expert layers; one instruction a
    # kernel sums them all
    assert flops_lm.slice_passes(run) == pytest.approx(0.5)
    assert [flops_qwen3_next.count(cfg, k) for k in
            ("gdn", "attention", "moe")] == [6, 2, 8]
    least = 0.5 * 6 * 6 * max(
        flops_qwen3_next.gated_delta_flops(cfg, 300) / 197e12,
        flops_qwen3_next.gated_delta_bytes(cfg, 300) / 819e9)
    assert got["gated_delta_roofline.qwen3_next"] == pytest.approx(
        100 * least / 4e-6)
    work = flops_qwen3_next.grouped_product_work(cfg, 6 * 300 * 8,
                                                 8 * 6 / 2)
    least = 0.5 * sum(max(ops / 197e12, nbytes / 819e9)
                      for ops, nbytes in work.values())
    assert got["moe_grouped_matmul_roofline.qwen3_next"] == pytest.approx(
        100 * least / 7e-6)
    core = 0.5 * 6 * 2 * max(
        flops_qwen3_next.attention_core_flops(cfg, 300) / 197e12,
        flops_qwen3_next.attention_core_bytes(cfg, 300) / 819e9)
    assert got["attention_core_roofline.qwen3_next"] == pytest.approx(
        100 * core / 1e-6)


def test_a_second_rungs_products_share_the_same_work(bench):
    """Steps that took the ladder's second rung show as three more grouped
    products: the work is counted once a product, the seconds all summed."""
    reader = bench.load_file_module("layer_metrics",
                                    "moe_grouped_matmul_roofline.qwen3_next")
    one = reader.read(synthetic_run(bench))
    run = synthetic_run(bench)
    run["trace"] = dict(run["trace"], device_ops=run["trace"]["device_ops"]
                        + [["%gmm.5 f32[768,32]", 2e-6],
                           ["%gmm.6 f32[768,32]", 2e-6],
                           ["%gmm.7 bf16[768,64]", 3e-6]])
    assert reader.read(run) == pytest.approx(one / 2)
    # only one product of the gate's width among the ten names: one
    # product's work over its seconds
    run["trace"] = dict(run["trace"],
                        device_ops=[["%gmm.1 f32[768,32]", 2e-6]])
    cfg = run["config"]
    ops, nbytes = flops_qwen3_next.grouped_product_work(
        cfg, 6 * 300 * 8, 24)["gate"]
    assert reader.read(run) == pytest.approx(
        100 * 0.5 * max(ops / 197e12, nbytes / 819e9) / 2e-6)


@pytest.mark.parametrize("name", NEW_READERS)
def test_a_new_reader_with_nothing_to_read_gives_none(bench, name):
    reader = bench.load_file_module("layer_metrics", name)
    # another family's run (the parent's program has no such module)
    other = synthetic_run(bench)
    other["config"] = dict(other["config"], family="nemotron_h")
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
            [f"%gated_delta.{i} bf16[2,384,128]", 1e-6] for i in range(3)] + [
            [f"%flash_attention_tiled.{i} f32[2,4,300,32]", 1e-6]
            for i in range(2)] + [[f"%gmm.{i} f32[768,32]", 1e-6]
                                  for i in range(7)])
        assert reader.read(many) is None


def test_operation_counts_reproduce_the_issues_arithmetic():
    cfg = json.load(open(os.path.join(
        BENCH, "configs", "qwen3_next_80b_a3b_ep8_stage16.json")))
    part = flops_qwen3_next.token_flops(cfg)
    # in and out products 67.4 M a layer, the recurrence's 5.77 M at chunk 64
    assert part["gdn"] - flops_qwen3_next.gated_delta_flops(cfg, 1) \
        == 2 * (2048 * 12288 + 2048 * 64 + 4096 * 2048)
    assert flops_qwen3_next.gated_delta_flops(cfg, 1) == 5_767_168
    assert part["routed"] == 6 * 2048 * 512             # three products
    assert part["shared"] == 6 * 2048 * 512 + 2 * 2048   # and its gate
    assert part["head"] == 2 * 2048 * 18992
    row = flops_qwen3_next.forward_flops(cfg, 8192, 1.25)
    assert row["total"] / 8192 == pytest.approx(1.701e9, rel=0.001)
    assert 2 * row["total"] == pytest.approx(27.9e12, rel=0.002)
    assert row["gdn"] / row["total"] == pytest.approx(0.516, abs=0.002)
    attention = row["attention"] + row["attention_projections"]
    assert attention / row["total"] == pytest.approx(0.286, abs=0.002)
    experts = row["router"] + row["routed"] + row["shared"]
    assert experts / row["total"] == pytest.approx(0.153, abs=0.002)
    assert row["head"] / row["total"] == pytest.approx(0.046, abs=0.002)
    assert [flops_qwen3_next.count(cfg, k) for k in
            ("gdn", "attention", "moe")] == [12, 4, 16]
    # the kernel's own counts, a token a layer: 24,832 B; at 16,384 tokens
    # a layer-step the bytes bound it, 0.50 ms against 0.48 of products
    assert flops_qwen3_next.gated_delta_bytes(cfg, 1) == 24_832
    least = flops_qwen3_next.gated_delta_bytes(cfg, 16384) / 819e9
    assert least == pytest.approx(0.4968e-3, rel=0.001)
    assert flops_qwen3_next.gated_delta_flops(cfg, 16384) / 197e12 < least
    # keys and values move once a KV head: 2, not 16
    assert flops_qwen3_next.attention_core_bytes(cfg, 8192) \
        == 8192 * 256 * (2 * 16 + 4 * 2 + 4 * 16)


def test_the_configuration_keeps_every_published_key():
    cfg = json.load(open(os.path.join(
        BENCH, "configs", "qwen3_next_80b_a3b_ep8_stage16.json")))
    # MODEL_CONFIG_CATALOG: a JSON-lines catalog of published configurations
    catalog = os.environ.get("MODEL_CONFIG_CATALOG", "")
    rows = [json.loads(line) for line in open(catalog)] \
        if catalog and os.path.exists(catalog) else []
    row = next((r for r in rows if r["name"]
                == "Qwen3-Next-80B-A3B-Instruct"), None)
    if row is None:
        pytest.skip("the catalog is not on this machine")
    reduced = ["num_hidden_layers", "num_experts", "vocab_size"]
    assert cfg["reduced"] == reduced and cfg["family"] == "qwen3_next"
    assert cfg["source"] == row["source_url"]
    for key, value in row["config"].items():
        if key in reduced:
            assert cfg["published"][key] == value, key
        else:
            assert cfg[key] == value, key
    assert (cfg["num_hidden_layers"], cfg["num_experts"],
            cfg["vocab_size"]) == (16, 64, 18992)
    assert 8 * cfg["vocab_size"] == row["config"]["vocab_size"]
    assert (cfg["router_width"], cfg["first_expert"]) == (512, 0)
    assert cfg["parameters"] == 3_879_901_440
    assert cfg["deployment"] and len(cfg["assumed"]) >= 8
    manifest = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    cell = "qwen3_next_score.seq8k"
    assert [c for c in manifest["workloads"] if c["name"] == cell] == [
        {"name": cell, "config": "qwen3_next_80b_a3b_ep8_stage16",
         "traffic": "seq8k", "chips": 1,
         "why": manifest["workloads"][-1]["why"]}]
    assert len(manifest["workloads"]) == 8
    assert sum(c["chips"] == 4 for c in manifest["workloads"]) == 1
    reads = {m["name"] for m in manifest["per_layer"]
             if cell in m.get("workloads", [])}
    assert {"score_mfu.qwen3_next", "moe_held_pairs_per_token.score",
            "moe_expert_load_max_over_mean.score",
            "compiles_in_window.score"} <= reads
    assert not any(name.endswith((".lfm2", ".jamba", ".lm", ".nemotron_h"))
                   for name in reads)
