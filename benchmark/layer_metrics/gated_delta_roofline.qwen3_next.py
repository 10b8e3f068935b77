"""``gated_delta_roofline.qwen3_next`` (%): the gated delta-rule kernel's
share of its roofline in the traced slice, for the family ``qwen3_next``:
the least time the chip could take for the recurrence of the rows and Gated
DeltaNet layers the slice holds (the larger of operations over the bf16 peak
and bytes over the HBM peak, ``benchmark/flops_qwen3_next.py``: ``q`` and
``k`` read once a KEY head, ``v`` read and ``o`` written once, ``g`` and
``beta`` in float32, the state never in HBM; the operations those of the
chunked form at the public kernel's chunk of 64, whatever chunk the kernel
takes, so the count is the same work whatever implements it or fuses around
it) over the summed device seconds of the kernel (``%gated_delta...``)
among the slice's operations. The program runs its whole stack as one scan
over layers, so the kernel is ONE instruction whose seconds sum every Gated
DeltaNet layer of every step in the slice. ``None`` for another family,
without a trace, where the kernel is not among the trace's ten largest
operations, and where more instructions of that name show than the program
has sites (a stack cut into runs: nothing is scaled on a guess). Layer:
kernels."""

from benchmark import flops_lm, flops_qwen3_next

# the places the program's one layer scan calls the kernel from
SITES = 1


def read(run: dict):
    cfg = run["config"]
    passes = flops_lm.slice_passes(run)
    found = flops_lm.op_seconds(run,
                                lambda name: name.startswith("%gated_delta"))
    if cfg.get("family") != "qwen3_next" or not passes \
            or len(found) != SITES:
        return None
    window = run["window"]["window_tokens"]
    row_layers = (passes * run["workload"]["rows"]
                  * flops_qwen3_next.count(cfg, "gdn"))
    work = [(row_layers * flops_qwen3_next.gated_delta_flops(cfg, window),
             row_layers * flops_qwen3_next.gated_delta_bytes(cfg, window))]
    return flops_lm.roofline_percent(work, sum(found.values()), run["peaks"])
