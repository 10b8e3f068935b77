"""``moe_grouped_matmul_roofline.qwen3_next`` (%): the three grouped expert
products' share of their roofline in the traced slice, for the family
``qwen3_next`` (gated experts: ``gate``, ``up`` and ``down``). The least
time the chip could take for them (for each product the larger of its
operations over the bf16 peak and its bytes over the HBM peak,
``benchmark/flops_qwen3_next.py``, at the picks the table's rows really sent
to held experts) over the summed device seconds of the operations that
compute them: the Pallas grouped product (``%gmm...``) or XLA's own
(``%ragged-dot...``), told apart by the width of their result
(``moe_intermediate_size``: gate and up, which share a signature;
``hidden_size``: down).

The program runs its whole stack as one scan over layers, so ONE such
operation is a product of every expert layer of every step in the slice
that took its rung of the expert layer's row-count ladder; a second rung
has operations of its own, which do the rest of the same products' work. So
the work of each product an operation of its width stands for is counted
ONCE (gate and up both where two or more of their width show, one of them
where one does), and the seconds of all that show are summed.
``trace_reduce`` keeps the ten largest operations of the slice; a product
none of whose operations is among them is left out of both sides. ``None``
without a trace, the load counts or any such operation, and where more show
than three products on each of two rungs (a program that cut its scan
otherwise: nothing is scaled on a guess). Layer: kernels."""

import re

from benchmark import flops_lm, flops_qwen3_next

KERNEL = re.compile(r"^%(gmm|ragged-dot)[-.\w]* (?:f32|bf16)\[\d+,(\d+)\]$")
# three products on each of at most two rungs that steps really take
MOST = 6


def read(run: dict):
    cfg, moe = run["config"], run["window"].get("moe")
    passes = flops_lm.slice_passes(run)
    if cfg.get("family") != "qwen3_next" or not moe or not passes:
        return None
    found = flops_lm.op_seconds(run, KERNEL.match)
    if not found or len(found) > MOST:
        return None
    widths = [int(KERNEL.match(name).group(2)) for name in found]
    f, d = cfg["moe_intermediate_size"], cfg["hidden_size"]
    if not set(widths) <= {f, d}:
        return None
    steps = flops_qwen3_next.count(cfg, "moe") * (
        run["workload"]["rows"] / run["workload"]["minibatch_size"])
    per_pass = flops_qwen3_next.grouped_product_work(
        cfg, moe["moe.held_pairs"], steps)
    names = (["gate", "up"][:widths.count(f)]
             + (["down"] if d in widths else []))
    work = [tuple(v * passes for v in per_pass[name]) for name in names]
    return flops_lm.roofline_percent(work, sum(found.values()), run["peaks"])
