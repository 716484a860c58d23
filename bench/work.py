"""Operations and bytes of one layer call, from its shape and declared
bit widths.

The counts are the layer's, never the implementation's: a packed
linear layer of ``rows x k -> n`` is ``2 * rows * k * n`` operations
whatever kernel runs it (the SDV kernel's limb and spill-tracker ops,
or the MXU's, are not counted), and its bytes are the weights at their
declared width, the activations at theirs and the outputs once.  So a
later change that moves a layer from one kernel to another leaves its
work unchanged, and a roofline share computed from it cannot pass 100%
unless the kernel time leaves out part of the work.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Tuple


@dataclasses.dataclass(frozen=True)
class Work:
    ops: float
    bytes: float

    def __add__(self, other: "Work") -> "Work":
        return Work(self.ops + other.ops, self.bytes + other.bytes)

    def __mul__(self, k: float) -> "Work":
        return Work(self.ops * k, self.bytes * k)

    __rmul__ = __mul__


ZERO = Work(0.0, 0.0)


def linear_call(rows: int, k: int, n: int, *, w_bits: int, a_bits: int,
                out_bytes: int) -> Work:
    """One ``[rows, k] @ [k, n]`` packed linear call."""
    return Work(ops=2.0 * rows * k * n,
                bytes=k * n * w_bits / 8 + rows * k * a_bits / 8
                + rows * n * out_bytes)


def conv_call(h: int, w: int, cin: int, cout: int, ksize: int, *,
              w_bits: int, a_bits: int, out_bytes: int = 4) -> Work:
    """One stride-1 'same' conv over an ``h x w x cin`` frame."""
    macs = h * w * cout * cin * ksize * ksize
    return Work(ops=2.0 * macs,
                bytes=cout * cin * ksize * ksize * w_bits / 8
                + h * w * cin * a_bits / 8 + h * w * cout * out_bytes)


def least_time(work: Work, peak: Dict[str, float], *, integer: bool
               ) -> Tuple[float, str]:
    """The least time the chip could take for ``work``: the larger of
    operations over the peak rate and bytes over the HBM bandwidth,
    with the resource that bounds it."""
    rate = peak["int8_ops_per_s"] if integer else peak["bf16_flops_per_s"]
    t_ops = work.ops / rate
    t_bytes = work.bytes / peak["hbm_bytes_per_s"]
    return (t_ops, "compute") if t_ops >= t_bytes else (t_bytes, "memory")


# ---------------------------------------------------------------------------
# decoder-only transformer (the granite configurations)
# ---------------------------------------------------------------------------

def decoder_linears(cfg: Dict) -> List[Tuple[str, int, int]]:
    """(name, k, n) of one decoder layer's projections."""
    d, ff = cfg["hidden_size"], cfg["intermediate_size"]
    hd = d // cfg["num_attention_heads"]
    q = cfg["num_attention_heads"] * hd
    kv = cfg["num_key_value_heads"] * hd
    return [("wq", d, q), ("wk", d, kv), ("wv", d, kv), ("wo", q, d),
            ("wi_gate", d, ff), ("wi_up", d, ff), ("mlp_wo", ff, d)]


def head_linear(cfg: Dict) -> Tuple[str, int, int]:
    return ("lm_head", cfg["hidden_size"], cfg["vocab_size"])


def step_linear_work(cfg: Dict, rows: int, *, head: bool) -> Work:
    """Every packed linear call of one model step over ``rows`` rows:
    the decoder layers' projections and, where the step produces
    logits, the LM head."""
    e = cfg["engine"]
    out_bytes = 2 if e["act_bits"] >= 16 else 4
    kw = dict(w_bits=e["weight_bits"], a_bits=e["act_bits"],
              out_bytes=out_bytes)
    total = ZERO
    for _, k, n in decoder_linears(cfg):
        total = total + linear_call(rows, k, n, **kw)
    total = total * cfg["num_hidden_layers"]
    if head and not cfg["tie_word_embeddings"]:
        _, k, n = head_linear(cfg)
        total = total + linear_call(rows, k, n, **kw)
    return total


def model_ops_per_token(cfg: Dict, context: int) -> float:
    """Operations the model needs for one token at ``context`` cached
    positions: 2 x the multiply-adds of every layer's projections, the
    attention over the context (scores and values) and the LM head."""
    d = cfg["hidden_size"]
    hd = d // cfg["num_attention_heads"]
    macs = sum(k * n for _, k, n in decoder_linears(cfg))
    macs += 2 * cfg["num_attention_heads"] * hd * context
    macs *= cfg["num_hidden_layers"]
    macs += d * cfg["vocab_size"]
    return 2.0 * macs


# ---------------------------------------------------------------------------
# UltraNet (the paper's conv model)
# ---------------------------------------------------------------------------

def ultranet_convs(cfg: Dict) -> List[Dict[str, int]]:
    """Per-conv shapes of the frame: the 3x3 stages, then the 1x1
    head (``kind`` says which)."""
    out, cin = [], cfg["in_channels"]
    h, w = cfg["frame"], cfg["frame"]
    for cout, ksize, pool in cfg["stages"]:
        out.append({"kind": "stage", "h": h, "w": w, "cin": cin,
                    "cout": cout, "k": ksize})
        cin = cout
        if pool:
            h, w = h // 2, w // 2
    out.append({"kind": "head", "h": h, "w": w, "cin": cin,
                "cout": cfg["head_channels"], "k": 1})
    return out


def ultranet_stage_work(cfg: Dict) -> Work:
    """The 3x3 stages of one frame (the convs ``bseg_conv2d`` runs)."""
    total = ZERO
    for c in ultranet_convs(cfg):
        if c["kind"] == "stage":
            total = total + conv_call(c["h"], c["w"], c["cin"], c["cout"],
                                      c["k"], w_bits=cfg["weight_bits"],
                                      a_bits=cfg["act_bits"])
    return total


def ultranet_frame_ops(cfg: Dict) -> float:
    """2 x every multiply-add of one frame, the head included."""
    return sum(2.0 * c["h"] * c["w"] * c["cin"] * c["cout"] * c["k"] ** 2
               for c in ultranet_convs(cfg))
