"""Operations and bytes of a conv / grouped-query / expert language model's
forward pass (family ``lfm2``), from the configuration's sizes alone.

As in ``flops.py`` and ``flops_lm.py``: one multiply-accumulate is two
operations, only the matrix products are counted (the convolution's three
taps a channel are 6 operations a channel beside 16,384: left out), nothing
comes from the compiler. Attention is counted causally (a query meets the
keys up to itself) and moves the keys and values once a KEY/VALUE head: a
kernel that repeated them to the query heads would read lower, not be
forgiven. The routed experts' work is counted at the picks the rows REALLY
sent to experts, which the driver reads from the program's own load counts
(``window["moe"]``); every expert is held, so that is ``num_experts_per_tok``
a token a layer.
"""

from __future__ import annotations


def kinds(cfg: dict) -> list:
    """``(operator, feed-forward)`` of every layer, in order."""
    return [(op, "dense" if i < cfg["num_dense_layers"] else "moe")
            for i, op in enumerate(cfg["layer_types"])]


def count(cfg: dict, kind: str) -> int:
    """How many layers have an operator or a feed-forward part ``kind``."""
    return sum(kind in pair for pair in kinds(cfg))


def a_period(cfg: dict, kind: str):
    """How many layers of ``kind`` one period of the layer pattern holds,
    where the layers after the dense ones are whole repeats of ONE period
    (the shortest); ``None`` where they are not (the published 24-entry
    list: its tail breaks the period). The program runs such a stack as a
    scan over the repeats (``docs/lm.md``), so one device instruction is
    one period position's kernel and its seconds in a trace are summed over
    the repeats: a reader divides a kind's work by this count to get what
    ONE instruction of that kind did."""
    rest = kinds(cfg)[cfg["num_dense_layers"]:]
    for p in range(1, len(rest) // 2 + 1):
        if len(rest) % p == 0 and all(rest[i] == rest[i % p]
                                      for i in range(len(rest))):
            return sum(kind in pair for pair in rest[:p]) or None
    return None


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def token_flops(cfg: dict) -> dict:
    """Operations of ONE token in ONE layer of each kind; ``routed`` is one
    (token, expert) pair's, ``head`` the whole head's."""
    d, hd = cfg["hidden_size"], head_dim(cfg)
    q_width = cfg["num_attention_heads"] * hd
    kv_width = cfg["num_key_value_heads"] * hd
    return {
        "conv": 2 * (d * 3 * d + d * d),
        "attention_projections": 2 * (2 * d * q_width + 2 * d * kv_width),
        "dense": 6 * d * cfg["intermediate_size"],
        "router": 2 * d * cfg["num_experts"],
        "routed": 6 * d * cfg["moe_intermediate_size"],
        "head": 2 * d * cfg["vocab_size"],
    }


def attention_core_flops(cfg: dict, window: int) -> int:
    """``q.k`` and ``p.v`` of one row of ``window`` tokens in one attention
    layer, every query head against the keys up to itself."""
    return (2 * cfg["num_attention_heads"] * 2 * head_dim(cfg)
            * window * (window + 1) // 2)


def attention_core_bytes(cfg: dict, window: int) -> int:
    """The least one row's attention core moves in one layer: ``q`` read
    and ``k``, ``v`` read ONCE A KEY/VALUE HEAD in the compute type (2
    bytes), the output written in float32."""
    hd = head_dim(cfg)
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return window * hd * (2 * hq + 2 * 2 * hkv + 4 * hq)


def forward_flops(cfg: dict, window: int, pairs_per_token: float) -> dict:
    """Forward operations of one row (a window of tokens), by part;
    ``pairs_per_token`` is the measured picks a token an expert layer."""
    part = token_flops(cfg)
    n_attn = count(cfg, "full_attention")
    parts = {
        "conv": count(cfg, "conv") * window * part["conv"],
        "attention_projections":
            n_attn * window * part["attention_projections"],
        "attention": n_attn * attention_core_flops(cfg, window),
        "dense": count(cfg, "dense") * window * part["dense"],
        "router": count(cfg, "moe") * window * part["router"],
        "routed": (count(cfg, "moe") * window * part["routed"]
                   * pairs_per_token),
        "head": window * part["head"],
    }
    parts["total"] = sum(parts.values())
    return parts


def grouped_product_work(cfg: dict, pairs: float, steps: float) -> dict:
    """``name -> (operations, bytes)`` of the three grouped expert products
    over ``pairs`` (token, expert) pairs met in ``steps`` expert-layer-steps
    (a step reads every expert's matrix once: at thousands of tokens a step
    no expert goes unvisited). Operands 2 bytes; gate and up come out in
    float32, down in the compute type."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = steps * cfg["num_experts"] * d * f * 2
    ops = 2 * pairs * d * f
    return {"gate": (ops, weights + pairs * (2 * d + 4 * f)),
            "up": (ops, weights + pairs * (2 * d + 4 * f)),
            "down": (ops, weights + pairs * (2 * f + 2 * d))}
