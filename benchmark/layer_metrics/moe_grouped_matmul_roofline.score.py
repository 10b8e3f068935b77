"""``moe_grouped_matmul_roofline.score`` (%): the grouped expert products'
share of their roofline in the traced slice. The least time the chip could
take for them — for each product the larger of its operations over the
bf16 peak and its bytes over the HBM peak (``benchmark/flops_lm.py``, at
the picks the table's rows really sent to held experts) — over the summed
device seconds of the operations that compute them: the Pallas grouped
product (``%gmm...``) or XLA's own (``%ragged-dot...``), told apart from
each other by the width of their result (``moe_intermediate_size``: gate
and up; ``hidden_size``: down). ``trace_reduce`` keeps the ten largest
operations of the slice; a product that is not among them is left out of
both sides. ``None`` without a trace, the load counts, or any such
operation. Layer: kernels."""

import re

from benchmark import flops_lm

KERNEL = re.compile(r"^%(gmm|ragged-dot)[-.\w]* (?:f32|bf16)\[\d+,(\d+)\]$")


def read(run: dict):
    moe = run["window"].get("moe")
    passes = flops_lm.slice_passes(run)
    if not moe or not passes:
        return None
    cfg = run["config"]
    found = flops_lm.op_seconds(run, KERNEL.match)
    steps = cfg["num_hidden_layers"] * (run["workload"]["rows"]
                                        / run["workload"]["minibatch_size"])
    per_pass = flops_lm.grouped_product_work(cfg, moe["moe.held_pairs"],
                                             steps)
    work = []
    for name in found:
        width = int(KERNEL.match(name).group(2))
        ops, nbytes = per_pass["down" if width == cfg["hidden_size"]
                               else "gate"]
        work.append((ops * passes, nbytes * passes))
    return flops_lm.roofline_percent(work, sum(found.values()), run["peaks"])
