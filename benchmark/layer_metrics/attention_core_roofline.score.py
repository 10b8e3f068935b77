"""``attention_core_roofline.score`` (%): the attention core's share of its
roofline in the traced slice: the least time the chip could take for the
causal ``q.k`` and ``p.v`` products of the rows the slice holds (the larger
of operations over the bf16 peak and bytes over the HBM peak,
``benchmark/flops_lm.py``) over the summed device seconds of the tiled
attention kernel (``%flash_attention_tiled...``). ``None`` where the trace
does not show the core as one operation among its ten largest. Layer:
kernels."""

from benchmark import flops_lm


def read(run: dict):
    passes = flops_lm.slice_passes(run)
    found = flops_lm.op_seconds(
        run, lambda name: name.startswith("%flash_attention_tiled"))
    if not passes or not found:
        return None
    cfg, window = run["config"], run["window"]["window_tokens"]
    row_layers = passes * run["workload"]["rows"] * cfg["num_hidden_layers"]
    work = [(row_layers * flops_lm.attention_core_flops(cfg, window),
             row_layers * flops_lm.attention_core_bytes(cfg, window))]
    return flops_lm.roofline_percent(work, sum(found.values()), run["peaks"])
