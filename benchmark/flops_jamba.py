"""Operations and bytes of a state-space / multi-query hybrid language
model's forward pass (family ``jamba``), from the configuration's sizes
alone.

As in ``flops.py``, ``flops_lm.py`` and ``flops_lfm2.py``: one
multiply-accumulate is two operations, only the matrix products are counted
towards the step's share of the peak (the convolution's four taps a channel
and the recurrence's elementwise work are left out, as ``flops_lfm2`` leaves
its taps out), nothing comes from the compiler. Attention is counted
causally and moves the keys and values once a KEY/VALUE head. The selective
scan has counts of its own (:func:`selective_scan_flops`,
:func:`selective_scan_bytes`): what the recurrence needs, and what a fused
kernel must move once.
"""

from __future__ import annotations


def kinds(cfg: dict) -> list:
    """The mixer of every layer, in order."""
    return ["attention"
            if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"]
            else "mamba" for i in range(cfg["num_hidden_layers"])]


def count(cfg: dict, kind: str) -> int:
    return kinds(cfg).count(kind)


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def d_inner(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def token_flops(cfg: dict) -> dict:
    """Matrix-product operations of ONE token in ONE layer of each kind;
    ``head`` the whole head's."""
    d, d_i, hd = cfg["hidden_size"], d_inner(cfg), head_dim(cfg)
    n, r = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    q_width = cfg["num_attention_heads"] * hd
    kv_width = cfg["num_key_value_heads"] * hd
    return {
        "mamba": 2 * (d * 2 * d_i + d_i * (r + 2 * n) + r * d_i + d_i * d),
        "attention_projections": 2 * (2 * d * q_width + 2 * d * kv_width),
        "mlp": 6 * d * cfg["intermediate_size"],
        "head": 2 * d * cfg["vocab_size"],
    }


def attention_core_flops(cfg: dict, window: int) -> int:
    """``q.k`` and ``p.v`` of one row of ``window`` tokens in one attention
    layer, every query head against the keys up to itself."""
    return (2 * cfg["num_attention_heads"] * 2 * head_dim(cfg)
            * window * (window + 1) // 2)


def forward_flops(cfg: dict, window: int) -> dict:
    """Forward matrix-product operations of one row (a window of tokens),
    by part."""
    part = token_flops(cfg)
    n_attn = count(cfg, "attention")
    parts = {
        "mamba": count(cfg, "mamba") * window * part["mamba"],
        "attention_projections":
            n_attn * window * part["attention_projections"],
        "attention": n_attn * attention_core_flops(cfg, window),
        "mlp": cfg["num_hidden_layers"] * window * part["mlp"],
        "head": window * part["head"],
    }
    parts["total"] = sum(parts.values())
    return parts


def selective_scan_flops(cfg: dict, window: int) -> int:
    """The recurrence of one row in one Mamba layer: per token and state
    element ``delta * A``, the ``exp``, ``exp * s``, ``(delta u) * B``, the
    sum, ``s * C`` and its sum over the state, the skip and the gate: 9 a
    state element."""
    return 9 * d_inner(cfg) * cfg["mamba_d_state"] * window


def selective_scan_bytes(cfg: dict, window: int) -> int:
    """The least one row's scan moves in one Mamba layer: ``c``, ``delta``
    and ``z`` read and ``y`` written once in the compute type (2 bytes a
    channel), ``B`` and ``C`` read in float32; the state never leaves the
    chip's fast memory, and ``A`` and ``D`` (a layer's, once) are left out."""
    return window * (4 * 2 * d_inner(cfg) + 2 * 4 * cfg["mamba_d_state"])
