"""``attention_core_roofline.nemotron_h`` (%): the grouped-query attention
core's share of its roofline in the traced slice, for the family
``nemotron_h`` (32 query heads of 128 on 2 key/value heads, no positional
term): the least time the chip could take for the causal ``q.k`` and
``p.v`` products of the rows and attention layers the slice holds (the
larger of operations over the bf16 peak and bytes over the HBM peak,
``benchmark/flops_nemotron_h.py``: **keys and values are counted once a
key/value head**, so a kernel that repeats them to the query heads reads
lower) over the summed device seconds of the tiled attention kernel
(``%flash_attention_tiled...``). The program runs its whole stack as one
scan over layers, so the kernel is ONE instruction whose seconds sum every
attention layer of every step in the slice. ``None`` for another family,
without a trace, where the core is not among the trace's ten largest
operations, and where more instructions of that name show than the program
has sites. Layer: kernels."""

from benchmark import flops_lm, flops_nemotron_h

# the places the program's one layer scan calls the kernel from
SITES = 1


def read(run: dict):
    cfg = run["config"]
    passes = flops_lm.slice_passes(run)
    found = flops_lm.op_seconds(
        run, lambda name: name.startswith("%flash_attention_tiled"))
    if cfg.get("family") != "nemotron_h" or not passes \
            or len(found) != SITES:
        return None
    window = run["window"]["window_tokens"]
    row_layers = (passes * run["workload"]["rows"]
                  * flops_nemotron_h.count(cfg, "attention"))
    work = [(row_layers * flops_nemotron_h.attention_core_flops(cfg, window),
             row_layers * flops_nemotron_h.attention_core_bytes(cfg, window))]
    return flops_lm.roofline_percent(work, sum(found.values()), run["peaks"])
