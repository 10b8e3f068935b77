"""``moe_expert_load_max_over_mean.score`` (ratio): the straggler ratio of
the held experts: the busiest (layer, expert)'s picks over the mean picks
of a held expert, over the cell's table (``window["moe"]["load"]``, the
program's per-layer routed-load counts as the driver read them in
set-up). 1.0 is an even load; in the deployment the busiest expert's chip
sets the pace of the exchange. Layer: model code."""


def read(run: dict):
    moe = run["window"].get("moe")
    if not moe or not moe.get("load"):
        return None
    loads = [n for layer in moe["load"] for n in layer]
    mean = sum(loads) / len(loads)
    return max(loads) / mean if mean else None
