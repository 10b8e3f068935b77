"""``split_call_share.score``: run by hand, on the CPU, beside the other
span-fed readers (``test_span_metrics.py``).

    JAX_PLATFORMS=cpu python -m pytest benchmark/tests -q -p no:cacheprovider

The reader on hand-made boundary records (calls with more ``plan/h2d``
records under their root than the root has ``minibatches``, over all calls
that count their minibatches; nothing to read where no root does), and
after a traced run of the tiny scoring cell: its minibatches are 48 KB, far
under the program's constant, so no call is split; with the constant
patched down to a quarter of a minibatch every call is.
"""

from __future__ import annotations

import json
import os

import pytest

from test_harness import TESTS, bench  # noqa: F401
from test_span_metrics import SPAN, traced  # noqa: F401

NAME = "split_call_share.score"
ENTRY = {"name": NAME, "unit": "%", "better": "higher",
         "source": "program_span", "layer": "plan / program",
         "moves": "score_rows_per_s", "workloads": ["tiny_resnet.table40"]}


def put_calls(calls):
    """One ``transform`` root a call with its ``plan/h2d`` records under
    it; ``calls`` is ``(minibatches or None, uploads)`` a call."""
    from mmlspark_tpu import obs
    from mmlspark_tpu.obs import runtime
    from mmlspark_tpu.obs.events import SpanRecord

    obs.clear()
    for k, (minibatches, uploads) in enumerate(calls):
        root = 100 * (k + 1)
        for u in range(uploads):
            runtime.record(SpanRecord(
                "plan/h2d", "plan", k * 1000 + u, 1, 1, "t", root + 1 + u,
                root, 2, None, root_id=root, nbytes=64))
        runtime.record(SpanRecord(
            "transform", "plan", k * 1000, 900, 1, "t", root, None, 0,
            None, root_id=root, rows=40, minibatches=minibatches))


@pytest.mark.parametrize("calls, share", [
    ([(4, 64), (4, 7)], 100.0),            # every minibatch cut; the head
    ([(4, 7), (4, 4)], 50.0),
    ([(4, 4), (3, 3)], 0.0),               # as many uploads as minibatches
    ([(None, 4), (None, 4)], None),        # no root counts its minibatches
    ([(None, 4), (4, 16)], 100.0),         # such a root is skipped
    ([(4, 0)], 0.0),
    ([], None),
])
def test_the_reader_on_hand_made_records(bench, calls, share):  # noqa: F811
    put_calls(calls)
    reader = bench.load_file_module("layer_metrics", NAME)
    window = {"window_s": 1.0, "calls": len(calls)}
    assert reader.read({"window": window}) == share


def test_only_the_windows_calls_are_read(bench):  # noqa: F811
    put_calls([(4, 4), (4, 16), (4, 16)])            # the first is set-up
    reader = bench.load_file_module("layer_metrics", NAME)
    assert reader.read({"window": {"window_s": 1.0, "calls": 2}}) == 100.0
    assert reader.read({"window": {"window_s": 1.0, "calls": 3}}) \
        == pytest.approx(200.0 / 3)


@pytest.mark.parametrize("pieces, share", [(False, 0.0), (True, 100.0)])
def test_the_tiny_cell(bench, traced, tmp_path, monkeypatch,  # noqa: F811
                       pieces, share):
    from mmlspark_tpu.core import plan

    with open(SPAN["manifest_path"], encoding="utf-8") as fh:
        manifest = json.load(fh)
    manifest["per_layer"].append(ENTRY)
    path = os.path.join(tmp_path, "manifest.json")
    with open(path, "w", encoding="utf-8") as fh:
        json.dump(manifest, fh)
    if pieces:      # 16 rows of 32 x 32 x 3 a minibatch: pieces of 4
        monkeypatch.setattr(plan, "_PIECE_MAX_BYTES", 4 * 32 * 32 * 3)
    result = bench.run(["--workload", "tiny_resnet.table40", "--seed",
                        "3000000034", "--seconds", "1", "--trace", "1"],
                       **{**SPAN, "manifest_path": path})
    assert result["correct"] is True, result["compared"]
    assert result["metrics"][NAME] == {"value": share, "unit": "%"}
    assert result["metrics"]["h2d_bytes_per_row.score"]["value"] > 0
