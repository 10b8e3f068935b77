"""``score_mfu.jamba`` (%): the whole state-space / multi-query hybrid
language model's scoring pass as a share of the chips' bf16 peak. Forward
matrix-product operations of the rows (token windows) scored, from the
configuration's sizes (``benchmark/flops_jamba.py``: attention counted
causally; the convolution's taps and the recurrence's elementwise work left
out), over the window's seconds (the host's feed included), over chips
times the peak of ``peaks.json``. ``None`` for another family. Layer: model
code."""

from benchmark import flops, flops_jamba


def read(run: dict):
    tokens = run["window"].get("window_tokens")
    if run["config"].get("family") != "jamba" or not tokens:
        return None
    return flops.peak_share_percent(
        run, flops_jamba.forward_flops(run["config"], tokens)["total"])
