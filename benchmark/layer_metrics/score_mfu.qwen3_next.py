"""``score_mfu.qwen3_next`` (%): the whole Gated DeltaNet / gated-attention /
expert language model's scoring pass as a share of the chips' bf16 peak.
Forward matrix-product operations of the rows (token windows) scored, from
the configuration's sizes (``benchmark/flops_qwen3_next.py``: attention
counted causally, the routed experts' three products at the held picks the
driver read from the program's load counts, the recurrence as the products
of its chunked form at the public kernel's chunk), over the window's seconds
(the host's feed included), over chips times the peak of ``peaks.json``.
``None`` for another family or without the load counts. Layer: model
code."""

from benchmark import flops, flops_qwen3_next


def read(run: dict):
    moe = run["window"].get("moe")
    if run["config"].get("family") != "qwen3_next" or not moe \
            or not moe.get("moe.tokens"):
        return None
    per_row = flops_qwen3_next.forward_flops(
        run["config"], run["window"]["window_tokens"],
        moe["moe.held_pairs"] / moe["moe.tokens"])["total"]
    return flops.peak_share_percent(run, per_row)
