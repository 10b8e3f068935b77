"""Operations and bytes of a Mamba-2 / ungated-expert / grouped-query
hybrid language model's forward pass (family ``nemotron_h``), from the
configuration's sizes alone.

As in ``flops.py``, ``flops_lm.py``, ``flops_lfm2.py`` and
``flops_jamba.py``: one multiply-accumulate is two operations, only the
matrix products are counted towards the step's share of the peak (the
convolution's four taps a channel, the decays' exponentials and the norms
are left out), nothing comes from the compiler. Attention is counted
causally and moves the keys and values once a KEY/VALUE head. The routed
experts' work is counted at the picks the rows REALLY sent to held experts,
which the driver reads from the program's own load counts
(``window["moe"]``); an expert is UNGATED: two products, not three. The
Mamba-2 recurrence is counted in its chunked ("state-space dual") form at
the PUBLISHED ``chunk_size``, whatever chunk a kernel takes, so the count is
the same work whatever implements it (:func:`ssd_scan_flops`,
:func:`ssd_scan_bytes`).
"""

from __future__ import annotations

KINDS = {"M": "mamba2", "E": "moe", "*": "attention"}


def kinds(cfg: dict) -> list:
    """The mixer of every layer, in order."""
    return [KINDS[ch] for ch in cfg["hybrid_override_pattern"]]


def count(cfg: dict, kind: str) -> int:
    return kinds(cfg).count(kind)


def d_inner(cfg: dict) -> int:
    return cfg["mamba_num_heads"] * cfg["mamba_head_dim"]


def ssd_scan_flops(cfg: dict, window: int) -> int:
    """The recurrence of one row in one Mamba-2 layer, as the matrix
    products of its chunked form at chunks of ``chunk_size`` positions: a
    head's masked ``[Q, Q] x [Q, P]`` product, its carried state's read-out
    and update (``[Q, N] x [N, P]`` each), and a group's ``C B^T``."""
    h, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    g, n, q = cfg["n_groups"], cfg["ssm_state_size"], cfg["chunk_size"]
    return window * (h * (2 * q * p + 2 * 2 * p * n) + g * 2 * q * n)


def ssd_scan_bytes(cfg: dict, window: int) -> int:
    """The least one row's scan moves in one Mamba-2 layer: ``x``, ``B``
    and ``C`` read and ``y`` written once in the compute type (2 bytes),
    the step sizes read in float32; the state never leaves the chip's fast
    memory, and ``A`` and ``D`` (a layer's, once) are left out."""
    gn = cfg["n_groups"] * cfg["ssm_state_size"]
    return window * (2 * (2 * d_inner(cfg) + 2 * gn)
                     + 4 * cfg["mamba_num_heads"])


def token_flops(cfg: dict) -> dict:
    """Matrix-product operations of ONE token in ONE layer of each kind;
    ``routed`` is one (token, held expert) pair's, ``head`` the whole
    head's, ``mamba2`` the projections and the recurrence's products."""
    d, d_i = cfg["hidden_size"], d_inner(cfg)
    wide = (2 * d_i + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
            + cfg["mamba_num_heads"])
    q_width = cfg["num_attention_heads"] * cfg["head_dim"]
    kv_width = cfg["num_key_value_heads"] * cfg["head_dim"]
    return {
        "mamba2": 2 * (d * wide + d_i * d) + ssd_scan_flops(cfg, 1),
        "attention_projections": 2 * (2 * d * q_width + 2 * d * kv_width),
        "router": 2 * d * cfg["router_width"],
        "routed": 4 * d * cfg["moe_intermediate_size"],
        "shared": (4 * d * cfg["moe_shared_expert_intermediate_size"]
                   * cfg["n_shared_experts"]),
        "head": 2 * d * cfg["vocab_size"],
    }


def attention_core_flops(cfg: dict, window: int) -> int:
    """``q.k`` and ``p.v`` of one row of ``window`` tokens in one attention
    layer, every query head against the keys up to itself."""
    return (2 * cfg["num_attention_heads"] * 2 * cfg["head_dim"]
            * window * (window + 1) // 2)


def attention_core_bytes(cfg: dict, window: int) -> int:
    """The least one row's attention core moves in one layer: ``q`` read
    and ``k``, ``v`` read ONCE A KEY/VALUE HEAD in the compute type (2
    bytes), the output written in float32."""
    hq, hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return window * cfg["head_dim"] * (2 * hq + 2 * 2 * hkv + 4 * hq)


def forward_flops(cfg: dict, window: int, held_pairs_per_token: float
                  ) -> dict:
    """Forward matrix-product operations of one row (a window of tokens),
    by part; ``held_pairs_per_token`` is the measured held picks a token an
    expert layer."""
    part = token_flops(cfg)
    n_attn, n_moe = count(cfg, "attention"), count(cfg, "moe")
    parts = {
        "mamba2": count(cfg, "mamba2") * window * part["mamba2"],
        "attention_projections":
            n_attn * window * part["attention_projections"],
        "attention": n_attn * attention_core_flops(cfg, window),
        "router": n_moe * window * part["router"],
        "routed": n_moe * window * part["routed"] * held_pairs_per_token,
        "shared": n_moe * window * part["shared"],
        "head": window * part["head"],
    }
    parts["total"] = sum(parts.values())
    return parts


def grouped_product_work(cfg: dict, pairs: float, steps: float) -> dict:
    """``name -> (operations, bytes)`` of the two grouped expert products
    over ``pairs`` (token, held expert) pairs met in ``steps``
    expert-layer-steps (a step reads every held expert's matrix once: at
    hundreds of pairs an expert a step none goes unvisited). Operands 2
    bytes; ``up`` comes out in float32, ``down`` in the compute type."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = steps * cfg["n_routed_experts"] * d * f * 2
    ops = 2 * pairs * d * f
    return {"up": (ops, weights + pairs * (2 * d + 4 * f)),
            "down": (ops, weights + pairs * (2 * f + 2 * d))}
