"""``score_mfu.lm`` (%): the whole language-model scoring pass's share of
the chips' bf16 peak. Forward operations of the rows (token windows)
scored, from the configuration's sizes (``benchmark/flops_lm.py``:
attention counted causally, routed experts at the picks the table's rows
really sent to held experts, which the driver reads from the program's
load counts), over the window's seconds (the host's feed included), over
chips times the peak of ``peaks.json``. Layer: model code."""

from benchmark import flops, flops_lm


def read(run: dict):
    moe = run["window"].get("moe")
    if not moe or not moe.get("moe.tokens"):
        return None
    per_row = flops_lm.forward_flops(
        run["config"], run["window"]["window_tokens"],
        moe["moe.held_pairs"] / moe["moe.tokens"])["total"]
    return flops.peak_share_percent(run, per_row)
