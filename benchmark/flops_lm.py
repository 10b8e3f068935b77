"""Operations and bytes of a latent-attention expert language model's
forward pass (family ``mistral4``), from the configuration's sizes alone.

As in ``flops.py``: one multiply-accumulate is two operations, only the
matrix products are counted, nothing comes from the compiler. Attention is
counted causally (a query meets the keys up to itself). The routed experts'
work is counted at the picks the rows REALLY sent to held experts, which
the driver reads from the program's own load counts (``window["moe"]``):
a share of a roofline reckoned on expected picks could read past 100 %.
"""

from __future__ import annotations


def _sizes(cfg: dict) -> tuple:
    return (cfg["hidden_size"], cfg["moe_intermediate_size"],
            cfg["num_attention_heads"],
            cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"],
            cfg["v_head_dim"])


def token_layer_flops(cfg: dict) -> dict:
    """Operations of ONE token in ONE layer, by part; ``routed`` is one
    (token, held expert) pair's."""
    d, f, h, dqk, dv = _sizes(cfg)
    rq, rkv = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    nope, rope = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    return {
        "projections": 2 * (d * rq + rq * h * dqk + d * (rkv + rope)
                            + rkv * h * (nope + dv) + h * dv * d),
        "router": 2 * d * cfg["router_width"],
        "shared": 6 * d * f * cfg["n_shared_experts"],
        "routed": 6 * d * f,
    }


def attention_core_flops(cfg: dict, window: int) -> int:
    """``q.k`` and ``p.v`` of one row of ``window`` tokens in one layer,
    each query against the keys up to itself."""
    _, _, h, dqk, dv = _sizes(cfg)
    return 2 * h * (dqk + dv) * window * (window + 1) // 2


def attention_core_bytes(cfg: dict, window: int) -> int:
    """The least one row's attention core moves in one layer: ``q``, ``k``
    and ``v`` read in the compute type (2 bytes), the output written in
    float32."""
    _, _, h, dqk, dv = _sizes(cfg)
    return window * h * (2 * (2 * dqk + dv) + 4 * dv)


def forward_flops(cfg: dict, window: int, held_pairs_per_token: float
                  ) -> dict:
    """Forward operations of one row (a window of tokens), by part;
    ``held_pairs_per_token`` is the measured held picks a token a layer."""
    part = token_layer_flops(cfg)
    layers = cfg["num_hidden_layers"]
    parts = {
        "projections": layers * window * part["projections"],
        "attention": layers * attention_core_flops(cfg, window),
        "router": layers * window * part["router"],
        "shared": layers * window * part["shared"],
        "routed": layers * window * part["routed"] * held_pairs_per_token,
        "head": 2 * window * cfg["hidden_size"] * cfg["vocab_size"],
    }
    parts["total"] = sum(parts.values())
    return parts


def grouped_product_work(cfg: dict, pairs: float, steps: float) -> dict:
    """``name -> (operations, bytes)`` of the three grouped expert products
    over ``pairs`` (token, held expert) pairs met in ``steps`` layer-steps
    (a step reads every held expert's matrix once: at hundreds of tokens a
    step no held expert goes unvisited). Operands 2 bytes, float32 out."""
    d, f = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = steps * cfg["n_routed_experts"] * d * f * 2
    ops = 2 * pairs * d * f
    return {"gate": (ops, weights + pairs * (2 * d + 4 * f)),
            "up": (ops, weights + pairs * (2 * d + 4 * f)),
            "down": (ops, weights + pairs * (2 * f + 4 * d))}


def roofline_percent(work: list, seconds: float, peaks: dict):
    """The least time the chip could take for ``work`` (``(operations,
    bytes)`` pairs, each the larger of operations over the peak rate and
    bytes over the peak bandwidth) over the ``seconds`` it took, in
    percent; ``None`` with nothing to divide by."""
    if not work or not seconds or peaks is None:
        return None
    least = sum(max(ops / peaks["bf16_flops_per_s"],
                    nbytes / peaks["hbm_bytes_per_s"])
                for ops, nbytes in work)
    return 100.0 * least / seconds


def slice_passes(run: dict):
    """How many passes over the table the traced slice holds, at the
    window's own rate (the slice is cut on the device's clock and holds
    whole and part steps alike); ``None`` without a trace or a window."""
    window, trace = run["window"], run["trace"]
    if not trace or not trace.get("window_s") or not window.get("rows"):
        return None
    rate = window["rows"] / window["window_s"]
    return rate * trace["window_s"] / run["workload"]["rows"]


def op_seconds(run: dict, match) -> dict:
    """``short name -> seconds`` of the traced slice's device operations
    whose short name ``match`` accepts."""
    trace = run["trace"]
    if not trace:
        return {}
    return {name: s for name, s in trace["device_ops"] if match(name)}
