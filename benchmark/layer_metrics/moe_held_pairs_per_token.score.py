"""``moe_held_pairs_per_token.score`` (pairs/token): the picks a token sends
to the experts held here, a layer (``moe.held_pairs`` over ``moe.tokens``,
the program's routed-load counters as the driver read them on the cell's
table in set-up and handed on in ``window["moe"]``). With 32 of 128 experts
held and 4 picks a token the expectation is 1.0; it sets the routed
experts' share of the operations. Layer: model code."""


def read(run: dict):
    moe = run["window"].get("moe")
    if not moe or not moe.get("moe.tokens"):
        return None
    return moe["moe.held_pairs"] / moe["moe.tokens"]
