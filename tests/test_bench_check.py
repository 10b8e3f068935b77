"""tools/bench_check.py — the perf-regression sentinel: tolerance-band
classification, exit-0 on the repo's real BENCH trajectory, exit-2 with
a named report on an injected regression."""

import importlib.util
import json
import os
import sys

import pytest

_TOOLS = os.path.join(os.path.dirname(__file__), "..", "tools")
_REPO = os.path.join(os.path.dirname(__file__), "..")


def _load():
    spec = importlib.util.spec_from_file_location(
        "mmlspark_tools_bench_check",
        os.path.join(_TOOLS, "bench_check.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


bench_check = _load()


def test_classification_rules():
    assert bench_check.classify("serve_rows_per_s") == "throughput"
    assert bench_check.classify("train_images_per_s_per_chip") \
        == "throughput"
    assert bench_check.classify("h2d_upload_mb_s") == "throughput"
    assert bench_check.classify("mxu_matmul_tf_s") == "throughput"
    assert bench_check.classify("serve_p99_ms") == "p99"
    assert bench_check.classify("serve_swap_p99_ms_during") == "p99"
    assert bench_check.classify("weight_bytes_ratio") == "exact"
    assert bench_check.classify("vs_baseline") is None
    assert bench_check.classify("bridge_batch_p50_ms") is None


def _rounds(*parsed):
    return [(n + 1, p) for n, p in enumerate(parsed)]


def test_throughput_and_p99_bands():
    prior = {"serve_rows_per_s": 100.0, "serve_p99_ms": 10.0}
    ok = bench_check.check_line(
        {"serve_rows_per_s": 91.0, "serve_p99_ms": 12.4},
        _rounds(prior))
    assert ok["verdict"] == "ok" and not ok["regressions"]
    bad = bench_check.check_line(
        {"serve_rows_per_s": 89.0, "serve_p99_ms": 20.0},
        _rounds(prior))
    assert bad["verdict"] == "regressed"
    assert sorted(r["key"] for r in bad["regressions"]) \
        == ["serve_p99_ms", "serve_rows_per_s"]
    p99 = [r for r in bad["regressions"]
           if r["key"] == "serve_p99_ms"][0]
    assert p99["class"] == "p99" and p99["ratio"] == 2.0


def test_load_wall_warm_gated_within_line_not_across_rounds():
    """The compile-cache load walls gate warm <= cold WITHIN one line
    (same box, same minute); absolute walls never gate across rounds
    (box weather), so a prior round with faster walls is irrelevant."""
    prior = {"serve_load_wall_cold_s": 0.1, "serve_load_wall_warm_s": 0.05}
    ok = bench_check.check_line(
        {"serve_load_wall_cold_s": 6.0, "serve_load_wall_warm_s": 0.4},
        _rounds(prior))
    assert ok["verdict"] == "ok" and not ok["regressions"]
    bad = bench_check.check_line(
        {"serve_load_wall_cold_s": 1.0, "serve_load_wall_warm_s": 1.5},
        _rounds(prior))
    assert bad["verdict"] == "regressed"
    row = bad["regressions"][0]
    assert row["key"] == "serve_load_wall_warm_s"
    assert row["class"] == "within-line" and row["best"] == 1.0
    # the within-line gate holds even with no archived rounds at all
    empty = bench_check.check_line(
        {"serve_load_wall_cold_s": 1.0, "serve_load_wall_warm_s": 1.5}, [])
    assert empty["verdict"] == "regressed"


def test_best_prior_round_is_per_metric():
    # throughput compares against the per-metric MAX across priors
    # (r2's 120), p99 against the per-metric MIN (r1's 8.0) — the best
    # prior is chosen per metric, not one chosen round
    r1 = {"serve_rows_per_s": 80.0, "serve_p99_ms": 8.0}
    r2 = {"serve_rows_per_s": 120.0, "serve_p99_ms": 14.0}
    rep = bench_check.check_line(
        {"serve_rows_per_s": 100.0, "serve_p99_ms": 9.9},
        _rounds(r1, r2))
    assert rep["verdict"] == "regressed"
    regs = {r["key"]: r for r in rep["regressions"]}
    assert list(regs) == ["serve_rows_per_s"]  # 100 < 0.9 * 120
    assert regs["serve_rows_per_s"]["best_round"] == 2
    p99_row = [r for r in rep["checked"]
               if r["key"] == "serve_p99_ms"][0]
    assert p99_row["best"] == 8.0 and p99_row["best_round"] == 1


def test_byte_ratios_exact():
    rep = bench_check.check_line(
        {"weight_bytes_ratio": 0.26},
        _rounds({"weight_bytes_ratio": 0.25}))
    assert rep["verdict"] == "regressed"
    assert rep["regressions"][0]["band"] == "== last"
    ok = bench_check.check_line(
        {"weight_bytes_ratio": 0.25},
        _rounds({"weight_bytes_ratio": 0.25}))
    assert ok["verdict"] == "ok"


def test_volatile_metrics_tracked_not_gated():
    rep = bench_check.check_line(
        {"inference_images_per_s_per_chip": 1.0},
        _rounds({"inference_images_per_s_per_chip": 100.0}))
    assert rep["verdict"] == "ok"
    assert rep["volatile"][0]["ratio"] == 0.01
    assert rep["volatile"][0]["gated"] is False


def test_new_and_non_numeric_keys_skipped():
    rep = bench_check.check_line(
        {"serve_rows_per_s": None, "brand_new_per_s": 5.0,
         "device": "TPU v5 lite"},
        _rounds({"serve_rows_per_s": 100.0}))
    assert rep["verdict"] == "ok"
    assert rep["new"] == ["brand_new_per_s"]


def test_multi_round_trajectory_exits_zero(tmp_path, capsys):
    """The acceptance pin: a several-round trajectory whose gated metrics
    stay in band passes the sentinel even while a VOLATILE host-I/O
    probe swings more than 2x between rounds (tracked, not gated). A
    fixture trajectory: the tree archives no bench rounds of its own
    (the link-era records were deleted in PR 21; the ledger is the
    driver's)."""
    rounds = [
        {"serve_rows_per_s": 100.0, "serve_p99_ms": 10.0,
         "inference_images_per_s_per_chip": 14000.0,
         "weight_bytes_ratio": 0.25, "device": "TPU v5 lite"},
        {"serve_rows_per_s": 104.0, "serve_p99_ms": 9.5,
         "inference_images_per_s_per_chip": 6000.0,
         "weight_bytes_ratio": 0.25, "device": "TPU v5 lite"},
        {"serve_rows_per_s": 99.0, "serve_p99_ms": 11.0,
         "inference_images_per_s_per_chip": 2500.0,
         "weight_bytes_ratio": 0.25, "new_this_round_per_s": 1.0,
         "device": "TPU v5 lite"},
    ]
    for n, parsed in enumerate(rounds, 1):
        with open(tmp_path / f"BENCH_r{n:02d}.json", "w") as fh:
            json.dump({"n": n, "parsed": parsed}, fh)
    rc = bench_check.main(["--repo", str(tmp_path)])
    out = capsys.readouterr().out
    assert rc == 0
    line = json.loads(out.splitlines()[0])
    assert line["bench_check"] == "ok"
    assert line["checked"] > 0


def test_injected_2x_p99_regression_exits_two(tmp_path, capsys):
    """The acceptance pin: a fixture trajectory with a 2x p99 blowup in
    the current line exits 2 and NAMES the regression."""
    with open(tmp_path / "BENCH_r01.json", "w") as fh:
        json.dump({"n": 1, "parsed": {
            "serve_rows_per_s": 100.0, "serve_p99_ms": 10.0,
            "weight_bytes_ratio": 0.25}}, fh)
    with open(tmp_path / "current.json", "w") as fh:
        json.dump({"serve_rows_per_s": 102.0, "serve_p99_ms": 20.0,
                   "weight_bytes_ratio": 0.25}, fh)
    rc = bench_check.main(["--repo", str(tmp_path),
                           "--current", str(tmp_path / "current.json")])
    out = capsys.readouterr().out
    assert rc == 2
    line = json.loads(out.splitlines()[0])
    assert line["bench_check"] == "regressed"
    assert line["regressions"] == ["serve_p99_ms"]
    assert "REGRESSION serve_p99_ms [p99]: 20.0" in out


def test_current_round_record_accepted(tmp_path, capsys):
    # --current also accepts a full round record ({"parsed": {...}})
    with open(tmp_path / "BENCH_r01.json", "w") as fh:
        json.dump({"n": 1, "parsed": {"serve_rows_per_s": 100.0}}, fh)
    with open(tmp_path / "current.json", "w") as fh:
        json.dump({"n": 2, "parsed": {"serve_rows_per_s": 95.0}}, fh)
    rc = bench_check.main(["--repo", str(tmp_path),
                           "--current", str(tmp_path / "current.json")])
    capsys.readouterr()
    assert rc == 0


def test_no_rounds_exits_two(tmp_path, capsys):
    rc = bench_check.main(["--repo", str(tmp_path)])
    assert rc == 2
    assert "no BENCH_r*.json" in capsys.readouterr().err
