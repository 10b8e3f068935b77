"""The span-fed per-layer metrics: run by hand, on the CPU, at tiny sizes.

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

``span_manifest.json`` is ``tiny_manifest.json`` with the readers of
``benchmark/span_read.py`` added for the same two tiny cells. Every such
reader gives a number after a traced run with the program's tracer never
enabled, the shares of a cell close to its window, and each returns
``None`` once the ring holds no boundary record.
"""

from __future__ import annotations

import os

import pytest

from test_harness import BENCH, TESTS, TINY, bench  # noqa: F401
from benchmark import trace_reduce

SPAN = {**TINY, "manifest_path": os.path.join(TESTS, "span_manifest.json"),
        "device_check": True}
SCORE = {"coerce_share.score", "upload_share.score",
         "fetch_wait_share.score", "assemble_share.score",
         "unspanned_share.score", "h2d_bytes_per_row.score"}
TRAIN = {"step_dispatch_share.train", "loss_fetch_wait_share.train"}
CELLS = {"tiny_resnet.table40": SCORE, "tiny_vit.b8": TRAIN}


@pytest.fixture()
def traced(bench, monkeypatch):  # noqa: F811
    """A traced run of a tiny cell on the CPU, whose trace has no device
    plane: the reduction is handed two operations."""
    from mmlspark_tpu import obs

    assert not obs.enabled()
    obs.clear()
    events = [("%fusion.0 = f32[1]{0} fusion(...)", 0, 30_000_000),
              ("%fusion.1 = f32[1]{0} fusion(...)", 60_000_000, 90_000_000)]
    monkeypatch.setattr(trace_reduce, "load_device_events",
                        lambda path: {"/device:TPU:0": events})
    monkeypatch.setattr(bench, "check_device", lambda chips: (
        bench.describe_device(), {"bf16_flops_per_s": 1e12}))

    def run(cell: str) -> dict:
        return bench.run(["--workload", cell, "--seed", "3000000021",
                          "--seconds", "1", "--trace", "1"], **SPAN)
    return run


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_every_span_reader_gives_a_number(traced, cell):
    metrics = traced(cell)["metrics"]
    assert CELLS[cell] <= set(metrics)
    for name in CELLS[cell]:
        assert metrics[name]["value"] >= 0, name


def test_the_score_shares_close_and_the_bytes_are_the_row_width(traced):
    metrics = traced("tiny_resnet.table40")["metrics"]
    shares = sum(metrics[n]["value"] for n in SCORE if "share" in n)
    # what is missing is dispatch self time (no metric of its own)
    assert 0.0 < shares <= 100.0 + 1e-6
    config = bench_config("tiny_resnet.json")
    width = config["image_size"] ** 2 * config["num_channels"]
    # 40 rows in minibatches of 16: the padded tail is uploaded too
    assert metrics["h2d_bytes_per_row.score"]["value"] == width * 48 / 40


def test_the_train_shares_stay_inside_the_window(traced):
    metrics = traced("tiny_vit.b8")["metrics"]
    total = sum(metrics[n]["value"] for n in TRAIN
                | {"input_wait_share.train"})
    assert 0.0 < total <= 100.0 + 1e-6


@pytest.mark.parametrize("cell", sorted(CELLS))
def test_no_boundary_record_no_metric(bench, cell):  # noqa: F811
    from mmlspark_tpu import obs

    obs.clear()
    window = {"window_s": 1.0, "calls": 3, "steps": 3}
    for name in CELLS[cell]:
        reader = bench.load_file_module("layer_metrics", name)
        assert reader.read({"window": window}) is None, name


def bench_config(name: str) -> dict:
    import json

    with open(os.path.join(TESTS, name), encoding="utf-8") as fh:
        return json.load(fh)
