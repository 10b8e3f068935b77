"""``moe_grouped_matmul_roofline.lfm2`` (%): the grouped expert products'
share of their roofline in the traced slice, for the family ``lfm2``. The
least time the chip could take for them (for each product the larger of its
operations over the bf16 peak and its bytes over the HBM peak,
``benchmark/flops_lfm2.py``, at the picks the table's rows really sent to
experts) over the summed device seconds of the operations that compute them:
the Pallas grouped product (``%gmm...``) or XLA's own (``%ragged-dot...``),
told apart by the width of their result (``moe_intermediate_size``: gate and
up; ``hidden_size``: down).

The program runs the expert layers as a scan over the repeats of the layer
pattern's period, so ONE such operation is one period position's product and
its seconds are summed over the repeats: it did ``1 / (expert layers a
period)`` of its product's work (``flops_lfm2.a_period``; 4 of the cell's 12
expert layers lie in a period, so an operation did 3 of the 12 layer-steps
of a step). ``trace_reduce`` keeps the ten largest operations of the slice;
a product that is not among them is left out of both sides (the cell's
period holds twelve alike, of which six show). ``None`` without a trace, the
load counts, a single period, or any such operation, and where more show
than a period has products (a program that cut its scan otherwise: nothing
is scaled on a guess; fewer cannot be told from the cut at ten). Layer:
kernels."""

import re

from benchmark import flops_lfm2, flops_lm

KERNEL = re.compile(r"^%(gmm|ragged-dot)[-.\w]* (?:f32|bf16)\[\d+,(\d+)\]$")


def read(run: dict):
    cfg, moe = run["config"], run["window"].get("moe")
    passes = flops_lm.slice_passes(run)
    if cfg.get("family") != "lfm2" or not moe or not passes:
        return None
    a_period = flops_lfm2.a_period(cfg, "moe")
    found = flops_lm.op_seconds(run, KERNEL.match)
    if not a_period or not found or len(found) > 3 * a_period:
        return None
    steps = flops_lfm2.count(cfg, "moe") * (
        run["workload"]["rows"] / run["workload"]["minibatch_size"])
    per_pass = flops_lfm2.grouped_product_work(cfg, moe["moe.held_pairs"],
                                               steps)
    share = passes / a_period
    work = []
    for name in found:
        width = int(KERNEL.match(name).group(2))
        ops, nbytes = per_pass["down" if width == cfg["hidden_size"]
                               else "gate"]
        work.append((ops * share, nbytes * share))
    return flops_lm.roofline_percent(work, sum(found.values()), run["peaks"])
