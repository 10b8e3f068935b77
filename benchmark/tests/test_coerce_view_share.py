"""``coerce_view_share.score``: run by hand, on the CPU, beside the other
span-fed readers (``test_span_metrics.py``).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

The reader on hand-made boundary records (rows whose coerce record copied
0 bytes over rows of all that say what they copied; nothing to read where
no record says), and after a traced run of the tiny scoring cell, whose
table is built from one matrix, so every row is handed back.
"""

from __future__ import annotations

import json
import os

import pytest

from test_harness import TESTS, bench  # noqa: F401
from test_span_metrics import SPAN, traced  # noqa: F401

NAME = "coerce_view_share.score"
ENTRY = {"name": NAME, "unit": "%", "better": "higher",
         "source": "program_span", "layer": "plan / program",
         "moves": "score_rows_per_s", "workloads": ["tiny_resnet.table40"]}


def put_calls(calls):
    """One ``transform`` root a call with its ``transform/coerce`` record
    under it; ``calls`` is ``(rows, nbytes copied or None)`` a call."""
    from mmlspark_tpu import obs
    from mmlspark_tpu.obs import runtime
    from mmlspark_tpu.obs.events import SpanRecord

    obs.clear()
    for k, (rows, nbytes) in enumerate(calls):
        root = 10 * (k + 1)
        runtime.record(SpanRecord(
            "transform/coerce", "plan", k * 1000, 100, 1, "t", root + 1,
            root, 1, None, root_id=root, rows=rows, nbytes=nbytes))
        runtime.record(SpanRecord(
            "transform", "plan", k * 1000, 900, 1, "t", root, None, 0,
            None, root_id=root, rows=rows))


@pytest.mark.parametrize("calls, share", [
    ([(40, 0), (40, 0)], 100.0),
    ([(40, 0), (40, 40 * 12)], 50.0),
    ([(10, 0), (30, 30 * 12)], 25.0),
    ([(40, 40 * 12)], 0.0),
    ([(40, None), (40, None)], None),      # the program before the counter
    ([(40, None), (40, 0)], 100.0),        # a declined coercion is skipped
    ([], None),
])
def test_the_reader_on_hand_made_records(bench, calls, share):  # noqa: F811
    put_calls(calls)
    reader = bench.load_file_module("layer_metrics", NAME)
    window = {"window_s": 1.0, "calls": len(calls)}
    assert reader.read({"window": window}) == share


def test_only_the_windows_calls_are_read(bench):  # noqa: F811
    put_calls([(40, 40 * 12), (40, 0), (40, 0)])     # the first is set-up
    reader = bench.load_file_module("layer_metrics", NAME)
    assert reader.read({"window": {"window_s": 1.0, "calls": 2}}) == 100.0


def test_the_tiny_cell_hands_every_row_back(bench, traced,  # noqa: F811
                                            tmp_path):
    with open(SPAN["manifest_path"], encoding="utf-8") as fh:
        manifest = json.load(fh)
    manifest["per_layer"].append(ENTRY)
    path = os.path.join(tmp_path, "manifest.json")
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(manifest, fh)
    result = bench.run(["--workload", "tiny_resnet.table40", "--seed",
                        "3000000027", "--seconds", "1", "--trace", "1"],
                       **{**SPAN, "manifest_path": path})
    assert result["correct"] is True, result["compared"]
    assert result["metrics"][NAME] == {"value": 100.0, "unit": "%"}
    assert result["metrics"]["coerce_share.score"]["value"] >= 0
