"""``score_mfu.lfm2`` (%): the whole conv / grouped-query / expert language
model's scoring pass as a share of the chips' bf16 peak. Forward operations
of the rows (token windows) scored, from the configuration's sizes
(``benchmark/flops_lfm2.py``: attention counted causally, keys and values as
held, routed experts at the picks the driver read from the program's load
counts), over the window's seconds (the host's feed included), over chips
times the peak of ``peaks.json``. ``None`` for another family or without the
load counts. Layer: model code."""

from benchmark import flops, flops_lfm2


def read(run: dict):
    moe = run["window"].get("moe")
    if run["config"].get("family") != "lfm2" or not moe \
            or not moe.get("moe.tokens"):
        return None
    per_row = flops_lfm2.forward_flops(
        run["config"], run["window"]["window_tokens"],
        moe["moe.held_pairs"] / moe["moe.tokens"])["total"]
    return flops.peak_share_percent(run, per_row)
