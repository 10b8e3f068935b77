"""``ssd_scan_roofline.nemotron_h`` (%): the Mamba-2 scan kernel's share of
its roofline in the traced slice, for the family ``nemotron_h``: the least
time the chip could take for the recurrence of the rows and Mamba-2 layers
the slice holds (the larger of operations over the bf16 peak and bytes over
the HBM peak, ``benchmark/flops_nemotron_h.py``: ``x``, ``B``, ``C`` read
and ``y`` written ONCE, the state never in HBM, the operations those of the
chunked form at the PUBLISHED ``chunk_size`` whatever chunk the kernel
takes; by that count the kernel is bound by bytes) over the summed device
seconds of the kernel (``%ssd_scan...``) among the slice's operations. The
program runs its whole stack as one scan over layers, so the kernel is ONE
instruction whose seconds sum every Mamba-2 layer of every step in the
slice. ``None`` for another family, without a trace, where the kernel is
not among the trace's ten largest operations, and where more instructions
of that name show than the program has sites (a stack cut into runs:
nothing is scaled on a guess). Layer: kernels."""

from benchmark import flops_lm, flops_nemotron_h

# the places the program's one layer scan calls the kernel from
SITES = 1


def read(run: dict):
    cfg = run["config"]
    passes = flops_lm.slice_passes(run)
    found = flops_lm.op_seconds(run,
                                lambda name: name.startswith("%ssd_scan"))
    if cfg.get("family") != "nemotron_h" or not passes \
            or len(found) != SITES:
        return None
    window = run["window"]["window_tokens"]
    row_layers = (passes * run["workload"]["rows"]
                  * flops_nemotron_h.count(cfg, "mamba2"))
    work = [(row_layers * flops_nemotron_h.ssd_scan_flops(cfg, window),
             row_layers * flops_nemotron_h.ssd_scan_bytes(cfg, window))]
    return flops_lm.roofline_percent(work, sum(found.values()), run["peaks"])
