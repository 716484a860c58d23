"""Operations and bytes of the granite-4.0-h-small configuration, by
the rule of ``work.py``: the layer's work from its shape and declared
bit widths, never the kernel's.

A dropless MoE call over ``rows`` routed (token, expert) rows that
touches ``touched`` experts is three projections per row (gate and up
``d -> f``, down ``f -> d``); its bytes are the touched experts'
weights at the declared width (an expert no row chose is not read),
the rows' activations at theirs and the outputs once.
"""
from __future__ import annotations

from typing import Dict

from work import Work, ZERO


def _dims(cfg: Dict) -> Dict[str, int]:
    d = cfg["hidden_size"]
    h, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return {"d": d, "hd": d // h, "h": h, "kv": kv,
            "di": cfg["mamba_expand"] * d,
            "gn": cfg["mamba_n_groups"] * cfg["mamba_d_state"],
            "heads": cfg["mamba_n_heads"], "n": cfg["mamba_d_state"],
            "p": cfg["mamba_d_head"], "taps": cfg["mamba_d_conv"],
            "e": cfg["num_local_experts"], "k": cfg["num_experts_per_tok"],
            "f": cfg["intermediate_size"],
            "sf": cfg["shared_intermediate_size"]}


def mixer_linear_params(cfg: Dict, kind: str) -> int:
    """Weights of a layer's mixer projections (the packed ones)."""
    s = _dims(cfg)
    d = s["d"]
    if kind == "mamba":
        return d * (2 * s["di"] + 2 * s["gn"] + s["heads"]) + s["di"] * d
    return d * s["hd"] * (s["h"] + 2 * s["kv"]) + s["h"] * s["hd"] * d


def expert_params(cfg: Dict) -> int:
    """Weights of one routed expert (gate, up, down)."""
    s = _dims(cfg)
    return 3 * s["d"] * s["f"]


def layer_linear_params(cfg: Dict, kind: str) -> int:
    """Every packed weight of one layer: its mixer's projections, all
    routed experts and the shared MLP (the router, the depthwise conv
    and the norms are left as made and not counted)."""
    s = _dims(cfg)
    return mixer_linear_params(cfg, kind) + s["e"] * expert_params(cfg) \
        + 3 * s["d"] * s["sf"]


def expert_ffn_call(cfg: Dict, rows: int, touched: int) -> Work:
    """One dropless MoE call's routed expert FFN (the grouped matmuls)."""
    s, e = _dims(cfg), cfg["engine"]
    act = e["act_bits"] / 8
    out = 2 if e["act_bits"] >= 16 else 4
    total = ZERO
    for k, n in ((s["d"], s["f"]), (s["d"], s["f"]), (s["f"], s["d"])):
        total = total + Work(
            ops=2.0 * rows * k * n,
            bytes=touched * k * n * e["weight_bits"] / 8
            + rows * k * act + rows * n * out)
    return total


def model_ops_per_token(cfg: Dict, context: int) -> float:
    """Operations the model needs for one token at ``context`` cached
    positions: 2 x the multiply-adds of every layer's mixer projections,
    the Mamba2 conv and state update (decay, input and output: 3 x
    heads x d_state x head size) or the attention over the context
    (scores and values), the router, the top-k experts and the shared
    MLP, and the tied head."""
    s = _dims(cfg)
    d = s["d"]
    macs = 0
    for kind in cfg["layer_types"]:
        macs += mixer_linear_params(cfg, kind)
        if kind == "mamba":
            macs += (s["di"] + 2 * s["gn"]) * s["taps"] \
                + 3 * s["heads"] * s["n"] * s["p"]
        else:
            macs += 2 * s["h"] * s["hd"] * context
        macs += d * s["e"] + s["k"] * expert_params(cfg) + 3 * d * s["sf"]
    macs += d * cfg["vocab_size"]
    return 2.0 * macs
