"""``score_mfu.nemotron_h`` (%): the whole Mamba-2 / ungated-expert /
grouped-query hybrid language model's scoring pass as a share of the chips'
bf16 peak. Forward matrix-product operations of the rows (token windows)
scored, from the configuration's sizes (``benchmark/flops_nemotron_h.py``:
attention counted causally, the routed experts' two products at the held
picks the driver read from the program's load counts, the recurrence as the
products of its chunked form at the published ``chunk_size``), over the
window's seconds (the host's feed included), over chips times the peak of
``peaks.json``. ``None`` for another family or without the load counts.
Layer: model code."""

from benchmark import flops, flops_nemotron_h


def read(run: dict):
    moe = run["window"].get("moe")
    if run["config"].get("family") != "nemotron_h" or not moe \
            or not moe.get("moe.tokens"):
        return None
    per_row = flops_nemotron_h.forward_flops(
        run["config"], run["window"]["window_tokens"],
        moe["moe.held_pairs"] / moe["moe.tokens"])["total"]
    return flops.peak_share_percent(run, per_row)
