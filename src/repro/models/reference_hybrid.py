"""Plain float32 reference of the moe_hybrid family (GraniteMoeHybrid).

Written from the layer equations alone, in numpy, for the CPU tests: it
reads the weights of a parameter tree and nothing else of the program.
Mamba2 is its sequential recurrence, one position at a time (not the
chunked SSD scan), attention is a causal softmax over the whole
sequence, and each routed expert runs on the tokens that chose it, one
token at a time (no dispatch, no capacity, nothing dropped).

    h   = embedding_multiplier * E[tok]
    per layer:  a = RMSNorm(h);  h = h + rm * (Mamba2(a) | Attn_NoPE(a))
                b = RMSNorm(h);  h = h + rm * (MoE(b) + SharedMLP(b))
    logits = RMSNorm(h) @ E^T / logits_scaling
"""
from __future__ import annotations

from typing import Any, Dict

import numpy as np


def _f32(a) -> np.ndarray:
    return np.asarray(a, np.float32)


def _rms(x, scale, eps):
    return x / np.sqrt(np.mean(x * x, -1, keepdims=True) + eps) * scale


def _silu(x):
    return x / (1.0 + np.exp(-x))


def _softplus(x):
    return np.logaddexp(0.0, x)


def mamba2(p: Dict[str, Any], a: np.ndarray, cfg) -> np.ndarray:
    """a [S, d] -> [S, d]: in projections, causal depthwise conv + SiLU,
    then per position h = exp(dt A) h + dt B x^T, y = C h + D x, and
    out_proj(RMSNorm(y * silu(z)))."""
    s = a.shape[0]
    hn, n, g = cfg.ssm_heads, cfg.ssm_state, cfg.ssm_groups
    hp = cfg.d_inner // hn
    z = a @ _f32(p["in_z"]["kernel"])
    xbc = np.concatenate([a @ _f32(p["in_x"]["kernel"]),
                          a @ _f32(p["in_bc"]["kernel"])], -1)
    dt = a @ _f32(p["in_dt"]["kernel"])
    w, bias = _f32(p["conv"]["w"]), _f32(p["conv"]["b"])     # [C, taps]
    taps = w.shape[1]
    padded = np.concatenate([np.zeros((taps - 1, xbc.shape[1]),
                                      np.float32), xbc])
    conv = np.stack([sum(w[:, q] * padded[t + q] for q in range(taps))
                     for t in range(s)]) + bias
    conv = _silu(conv)
    xs = conv[:, :cfg.d_inner].reshape(s, hn, hp)
    bs = conv[:, cfg.d_inner:cfg.d_inner + g * n].reshape(s, g, n)
    cs = conv[:, cfg.d_inner + g * n:].reshape(s, g, n)
    dt = _softplus(dt + _f32(p["dt_bias"]))                    # [S, H]
    a_neg = -np.exp(_f32(p["a_log"]))
    rep = hn // g
    state = np.zeros((hn, n, hp), np.float32)
    ys = []
    for t in range(s):
        bt = np.repeat(bs[t], rep, axis=0)                     # [H, N]
        ct = np.repeat(cs[t], rep, axis=0)
        state = np.exp(dt[t] * a_neg)[:, None, None] * state \
            + dt[t][:, None, None] * bt[:, :, None] * xs[t][:, None, :]
        ys.append(np.einsum("hn,hnp->hp", ct, state)
                  + _f32(p["d_skip"])[:, None] * xs[t])
    y = np.stack(ys).reshape(s, cfg.d_inner)
    y = _rms(y * _silu(z), _f32(p["norm"]["scale"]), cfg.rms_eps)
    return y @ _f32(p["out_proj"]["kernel"])


def attention(p: Dict[str, Any], a: np.ndarray, cfg) -> np.ndarray:
    """a [S, d] -> [S, d]: causal GQA with no position embedding,
    scores scaled by ``attn_scale``."""
    s = a.shape[0]
    h, kv, hd = cfg.n_heads, cfg.n_kv, cfg.hd
    q = (a @ _f32(p["wq"]["kernel"])).reshape(s, h, hd)
    k = (a @ _f32(p["wk"]["kernel"])).reshape(s, kv, hd)
    v = (a @ _f32(p["wv"]["kernel"])).reshape(s, kv, hd)
    k, v = np.repeat(k, h // kv, axis=1), np.repeat(v, h // kv, axis=1)
    sc = np.einsum("qhd,khd->hqk", q, k) * cfg.attn_scale
    sc = np.where(np.tril(np.ones((s, s), bool)), sc, -np.inf)
    pr = np.exp(sc - sc.max(-1, keepdims=True))
    pr /= pr.sum(-1, keepdims=True)
    o = np.einsum("hqk,khd->qhd", pr, v).reshape(s, h * hd)
    return o @ _f32(p["wo"]["kernel"])


def moe(p: Dict[str, Any], b: np.ndarray, cfg) -> np.ndarray:
    """b [S, d] -> [S, d]: for each token, its top-k experts by router
    logit, weighted by the softmax of those k logits, each expert's
    SwiGLU FFN on that token alone; plus the shared MLP."""
    logits = b @ _f32(p["router"]["kernel"])
    out = np.zeros_like(b)
    for t in range(b.shape[0]):
        top = np.argsort(-logits[t], kind="stable")[:cfg.top_k]
        wts = np.exp(logits[t, top] - logits[t, top].max())
        wts /= wts.sum()
        for e, wt in zip(top, wts):
            g = b[t] @ _f32(p["wi_gate"][e])
            u = b[t] @ _f32(p["wi_up"][e])
            out[t] += wt * ((_silu(g) * u) @ _f32(p["wo"][e]))
    sp = p["shared"]
    g = b @ _f32(sp["wi_gate"]["kernel"])
    u = b @ _f32(sp["wi_up"]["kernel"])
    return out + (_silu(g) * u) @ _f32(sp["wo"]["kernel"])


def forward(cfg, params: Dict[str, Any], tokens: np.ndarray) -> np.ndarray:
    """Logits [B, S, vocab_padded] of ``tokens`` [B, S], float32."""
    period = cfg.layer_period
    n_per = cfg.n_layers // len(period)
    emb = _f32(params["embed"])
    eps, rm = cfg.rms_eps, cfg.residual_multiplier
    out = []
    for row in np.asarray(tokens):
        x = emb[row] * cfg.embedding_multiplier
        for i in range(n_per):
            for j, kind in enumerate(period):
                lp = _layer(params["blocks"][f"l{j}"], i)
                a = _rms(x, _f32(lp["ln_mix"]["scale"]), eps)
                h = mamba2(lp["mamba"], a, cfg) if kind == "mamba" \
                    else attention(lp["attn"], a, cfg)
                x = x + rm * h
                b = _rms(x, _f32(lp["ln_mlp"]["scale"]), eps)
                x = x + rm * moe(lp["moe"], b, cfg)
        x = _rms(x, _f32(params["ln_f"]["scale"]), eps)
        out.append(x @ emb.T / cfg.logits_scaling)
    return np.stack(out)


def _layer(tree, i: int):
    """Scan step ``i`` of a stacked parameter subtree."""
    if isinstance(tree, dict):
        return {k: _layer(v, i) for k, v in tree.items()}
    return np.asarray(tree)[i]
