"""``attention_core_roofline.lfm2`` (%): the grouped-query attention core's
share of its roofline in the traced slice, for the family ``lfm2``: the
least time the chip could take for the causal ``q.k`` and ``p.v`` products
of the rows the slice holds (the larger of operations over the bf16 peak and
bytes over the HBM peak, ``benchmark/flops_lfm2.py``: **keys and values are
counted once a key/value head**, so a kernel that repeats them to the query
heads reads lower) over the summed device seconds of the tiled attention
kernel (``%flash_attention_tiled...``). One such operation is one period
position's kernel, its seconds summed over the scan's repeats, so it did ``1
/ (attention layers a period)`` of the attention layers' work
(``flops_lfm2.a_period``; the cell's period holds one). ``None`` where the
trace does not show the core among its ten largest operations, the layers
are no single period, or the kernels found are not one a period position
(a program that cut its scan otherwise: nothing is scaled on a guess).
Layer: kernels."""

from benchmark import flops_lfm2, flops_lm


def read(run: dict):
    cfg = run["config"]
    passes = flops_lm.slice_passes(run)
    found = flops_lm.op_seconds(
        run, lambda name: name.startswith("%flash_attention_tiled"))
    if cfg.get("family") != "lfm2" or not passes or not found:
        return None
    if len(found) != flops_lfm2.a_period(cfg, "full_attention"):
        return None
    window = run["window"]["window_tokens"]
    row_layers = (passes * run["workload"]["rows"]
                  * flops_lfm2.count(cfg, "full_attention"))
    work = [(row_layers * flops_lfm2.attention_core_flops(cfg, window),
             row_layers * flops_lfm2.attention_core_bytes(cfg, window))]
    return flops_lm.roofline_percent(work, sum(found.values()), run["peaks"])
