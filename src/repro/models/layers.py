"""Layer library: norms, projections, rotary attention (chunked /
flash-style), gated MLPs, and capacity-based MoE.

All layers are functional: ``*_init(ini, ...) -> param pytree (P
leaves)`` and ``*_apply(params, x, ...) -> y`` with plain jnp values.
Attention is streaming (running-max softmax over KV chunks) so 32k
prefill never materializes an S x S score matrix.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from .param import Init, P
from .quantized import is_packed, is_sdv, materialize, sdv_matmul_apply
from . import shard_ctx


def mat(w, dtype):
    """Materialize a kernel: PackedLinear -> dense, else cast."""
    return materialize(w, dtype) if is_packed(w) else w.astype(dtype)


# ---------------------------------------------------------------------------
# norms
# ---------------------------------------------------------------------------

def rmsnorm_init(ini: Init, dim: int):
    return {"scale": ini.ones((dim,), (None,), dtype=jnp.float32)}


def rmsnorm_apply(params, x, *, eps: float = 1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    # normalize in f32, but cast before the (broadcast) scale multiply:
    # the f32->bf16 boundary then sits BEFORE the TP resharding point,
    # halving the residual-stream all-gather bytes (§Perf iteration)
    y = (xf * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    return y * params["scale"].astype(x.dtype)


# ---------------------------------------------------------------------------
# dense
# ---------------------------------------------------------------------------

def dense_init(ini: Init, d_in: int, d_out: int, axes, *, bias: bool = False,
               std: Optional[float] = None):
    std = std if std is not None else 1.0 / math.sqrt(d_in)
    p = {"kernel": ini.normal((d_in, d_out), axes, std=std)}
    if bias:
        p["bias"] = ini.zeros((d_out,), (axes[1],))
    return p


def dense_apply(params, x):
    w = params["kernel"]
    if is_sdv(w):
        # arithmetic packing: the GEMM runs on the SDV datapath through
        # the packed_matmul dispatch layer (never materialized)
        y = sdv_matmul_apply(w, x)
    elif hasattr(w, "qat_apply"):
        # QAT container (train/qat/ste.QATLinear): STE fake-quant
        # forward, optionally through the packed dispatch — duck-typed
        # so the model library never imports the training stack
        y = w.qat_apply(x)
    else:
        y = x @ mat(w, x.dtype)
    if "bias" in params:
        y = y + params["bias"].astype(x.dtype)
    return y


# ---------------------------------------------------------------------------
# rotary position embedding
# ---------------------------------------------------------------------------

def rope(x: jnp.ndarray, positions: jnp.ndarray, *, theta: float = 10000.0):
    """x [B, S, H, D]; positions [B, S] (int32)."""
    d = x.shape[-1]
    half = d // 2
    freq = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=jnp.float32)
                   / half)
    ang = positions[..., None].astype(jnp.float32) * freq       # [B,S,half]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# ---------------------------------------------------------------------------
# attention (GQA, streaming softmax, optional sliding window / cross)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class AttnConfig:
    d_model: int
    n_heads: int
    n_kv: int
    head_dim: int
    qkv_bias: bool = False
    rope_theta: float = 10000.0
    window: Optional[int] = None     # sliding-window size (local attention)
    softcap: Optional[float] = None
    use_rope: bool = True
    free_qkv_sharding: bool = False  # skip explicit q/k/v constraints


def attention_init(ini: Init, cfg: AttnConfig):
    h, kv, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    return {
        "wq": dense_init(ini, cfg.d_model, h * hd, ("fsdp", "tp"),
                         bias=cfg.qkv_bias),
        "wk": dense_init(ini, cfg.d_model, kv * hd, ("fsdp", "tp"),
                         bias=cfg.qkv_bias),
        "wv": dense_init(ini, cfg.d_model, kv * hd, ("fsdp", "tp"),
                         bias=cfg.qkv_bias),
        "wo": dense_init(ini, h * hd, cfg.d_model, ("tp", "fsdp")),
    }


def _stream_attend(q, k, v, *, q_start: int, causal: bool,
                   window: Optional[int], chunk: int, softcap=None):
    """Two-level streaming softmax attention (flash-style, pure JAX).

    q [B, Sq, KV, R, D] (R = heads per kv group), k/v [B, Sk, KV, D].
    Positions of q are q_start..q_start+Sq-1; k/v cover 0..Sk-1.

    An outer ``lax.scan`` walks query chunks; an inner ``fori_loop``
    with *dynamic* bounds walks only the KV chunks each query chunk can
    see (causal upper bound, sliding-window lower bound) — memory is
    O(chunk^2) per head group and causal/windowed FLOPs are not spent
    on fully-masked blocks.  Returns [B, Sq, KV, R, D].
    """
    b, sq, kvh, r, d = q.shape
    sk = k.shape[1]
    scalef = 1.0 / math.sqrt(d)
    nkv = -(-sk // chunk)
    kp = jnp.pad(k, ((0, 0), (0, nkv * chunk - sk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, nkv * chunk - sk), (0, 0), (0, 0)))
    nq = -(-sq // chunk)
    qp = jnp.pad(q, ((0, 0), (0, nq * chunk - sq), (0, 0), (0, 0), (0, 0)))
    qc_all = qp.reshape(b, nq, chunk, kvh, r, d).transpose(1, 0, 2, 3, 4, 5)

    def outer(_, inp):
        qc, qi = inp                                  # [B,c,G,R,D]
        qf = qc.astype(jnp.float32)
        qpos = q_start + qi * chunk + jnp.arange(chunk)

        def inner(ci, carry):
            m, l, acc = carry
            kch = jax.lax.dynamic_slice_in_dim(
                kp, ci * chunk, chunk, axis=1).astype(jnp.float32)
            vch = jax.lax.dynamic_slice_in_dim(
                vp, ci * chunk, chunk, axis=1).astype(jnp.float32)
            kpos = ci * chunk + jnp.arange(chunk)
            s = jnp.einsum("bqgrd,bkgd->bqgrk", qf, kch) * scalef
            if softcap is not None:
                s = jnp.tanh(s / softcap) * softcap
            mask = (kpos < sk)[None, None, None, None, :]
            if causal:
                mask = mask & (kpos[None, None, None, None, :]
                               <= qpos[None, :, None, None, None])
            if window is not None:
                mask = mask & (kpos[None, None, None, None, :]
                               > qpos[None, :, None, None, None] - window)
            s = jnp.where(mask, s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bqgrk,bkgd->bqgrd", p, vch)
            return (m_new, l_new, acc_new)

        # dynamic KV-chunk range visible to this query chunk
        if causal:
            hi = jnp.minimum(
                nkv, (q_start + (qi + 1) * chunk + chunk - 1) // chunk)
        else:
            hi = nkv
        if window is not None:
            lo = jnp.maximum(0, (q_start + qi * chunk - window) // chunk)
        else:
            lo = 0
        m0 = jnp.full((b, chunk, kvh, r), -1e30, jnp.float32)
        l0 = jnp.zeros((b, chunk, kvh, r), jnp.float32)
        a0 = jnp.zeros((b, chunk, kvh, r, d), jnp.float32)
        m, l, acc = jax.lax.fori_loop(lo, hi, inner, (m0, l0, a0))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    _, outs = jax.lax.scan(outer, None,
                           (qc_all, jnp.arange(nq, dtype=jnp.int32)))
    out = outs.transpose(1, 0, 2, 3, 4, 5).reshape(b, nq * chunk, kvh, r, d)
    return out[:, :sq]


def _stream_attend_diff(q, k, v, *, q_start: int, causal: bool,
                        window: Optional[int], chunk: int, softcap=None):
    """Differentiable variant: the query-chunk loop is a *python* loop,
    so every KV range is static and the inner walk is a reverse-mode-
    friendly ``lax.scan`` — while-loops (dynamic fori bounds) cannot be
    transposed by JAX.  Same math, same causal-FLOPs saving."""
    b, sq, kvh, r, d = q.shape
    sk = k.shape[1]
    scalef = 1.0 / math.sqrt(d)
    nkv = -(-sk // chunk)
    kp = jnp.pad(k, ((0, 0), (0, nkv * chunk - sk), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, nkv * chunk - sk), (0, 0), (0, 0)))
    nq = -(-sq // chunk)
    qp = jnp.pad(q, ((0, 0), (0, nq * chunk - sq), (0, 0), (0, 0), (0, 0)))

    outs = []
    for qi in range(nq):
        # operands stay bf16 (MXU-style), accumulation is f32 — halves
        # the backward-pass cotangent all-gathers (§Perf iteration)
        qf = qp[:, qi * chunk:(qi + 1) * chunk]
        qpos = q_start + qi * chunk + jnp.arange(chunk)
        if causal:
            hi = min(nkv, -(-(q_start + (qi + 1) * chunk) // chunk))
        else:
            hi = nkv
        lo = max(0, (q_start + qi * chunk - window) // chunk) \
            if window is not None else 0
        n_steps = max(1, hi - lo)
        kc = kp[:, lo * chunk:(lo + n_steps) * chunk].reshape(
            b, n_steps, chunk, kvh, d).transpose(1, 0, 2, 3, 4)
        vc = vp[:, lo * chunk:(lo + n_steps) * chunk].reshape(
            b, n_steps, chunk, kvh, d).transpose(1, 0, 2, 3, 4)

        def step(carry, inp):
            m, l, acc = carry
            kch, vch, ci = inp
            kpos = ci * chunk + jnp.arange(chunk)
            s = jnp.einsum("bqgrd,bkgd->bqgrk", qf, kch,
                           preferred_element_type=jnp.float32) * scalef
            if softcap is not None:
                s = jnp.tanh(s / softcap) * softcap
            mask = (kpos < sk)[None, None, None, None, :]
            if causal:
                mask = mask & (kpos[None, None, None, None, :]
                               <= qpos[None, :, None, None, None])
            if window is not None:
                mask = mask & (kpos[None, None, None, None, :]
                               > qpos[None, :, None, None, None] - window)
            s = jnp.where(mask, s, -1e30)
            m_new = jnp.maximum(m, jnp.max(s, axis=-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + jnp.sum(p, axis=-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bqgrk,bkgd->bqgrd", p.astype(q.dtype), vch,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, chunk, kvh, r), -1e30, jnp.float32)
        l0 = jnp.zeros((b, chunk, kvh, r), jnp.float32)
        a0 = jnp.zeros((b, chunk, kvh, r, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(
            step, (m0, l0, a0),
            (kc, vc, jnp.arange(lo, lo + n_steps, dtype=jnp.int32)))
        outs.append((acc / jnp.maximum(l, 1e-30)[..., None]).astype(q.dtype))
    out = jnp.concatenate(outs, axis=1)
    return out[:, :sq]


def attention_apply(params, cfg: AttnConfig, x, *, positions,
                    kv: Optional[tuple] = None, causal: bool = True,
                    q_start: int = 0, chunk: int = 1024,
                    differentiable: bool = True):
    """Self- (kv=None) or cross- (kv=(k_in, v_in) activations) attention.

    x [B, S, d]; returns ([B, S, d], (k, v) of this call).
    """
    b, s, _ = x.shape
    h, g, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    r = h // g
    q = dense_apply(params["wq"], x).reshape(b, s, h, hd)
    if kv is None:
        k = dense_apply(params["wk"], x).reshape(b, s, g, hd)
        v = dense_apply(params["wv"], x).reshape(b, s, g, hd)
        if cfg.use_rope:
            q = rope(q, positions, theta=cfg.rope_theta)
            k = rope(k, positions, theta=cfg.rope_theta)
    else:
        src_k, src_v = kv
        sk = src_k.shape[1]
        k = dense_apply(params["wk"], src_k).reshape(b, sk, g, hd)
        v = dense_apply(params["wv"], src_v).reshape(b, sk, g, hd)
    tp = shard_ctx.tp_size()
    if not cfg.free_qkv_sharding:
        if h % tp == 0:
            # head-parallel attention (heads divide the model axis)
            q = shard_ctx.constrain(q, "batch", None, "tp", None)
            k = shard_ctx.constrain(k, "batch", None,
                                    "tp" if g % tp == 0 else None, None)
            v = shard_ctx.constrain(v, "batch", None,
                                    "tp" if g % tp == 0 else None, None)
        else:
            # heads don't divide the model axis: leave placement to
            # GSPMD
            pass
    qg = q.reshape(b, s, g, r, hd)
    attend = _stream_attend_diff if differentiable else _stream_attend
    out = attend(qg, k, v, q_start=q_start, causal=causal,
                 window=cfg.window, chunk=min(chunk, max(s, 16)),
                 softcap=cfg.softcap)
    out = out.reshape(b, s, h * hd)
    return dense_apply(params["wo"], out), (k, v)


def _quantize_kv(t):
    """[B, 1, G, hd] -> (int8 values, [B, 1, G] f32 scale)."""
    amax = jnp.max(jnp.abs(t.astype(jnp.float32)), axis=-1)
    scale = jnp.maximum(amax, 1e-8) / 127.0
    q = jnp.round(t.astype(jnp.float32) / scale[..., None])
    return jnp.clip(q, -127, 127).astype(jnp.int8), scale


def decode_attention(params, cfg: AttnConfig, x, *, cache_k, cache_v,
                     cache_index, cache_k_scale=None, cache_v_scale=None,
                     write_mask=None):
    """Single-token decode against a KV cache.

    x [B, 1, d]; cache_k/v [B, S_max, KV, hd]; cache_index int32 —
    scalar or per-slot [B]: each slot's count of valid entries (the
    new token goes to that slot's position).  Per-slot positions are
    what let a fresh session join a freed batch slot mid-wave.
    ``write_mask`` [B] bool (optional): rows with False skip the KV
    write — slots that are mid-prefill in a mixed iteration, whose
    index must not move here; their outputs are never read.
    With ``cache_*_scale`` the cache is int8 per-(position, head)
    quantized — the paper's packing idea applied to the decode memory
    roofline (cache traffic halves vs bf16).
    Returns (y, new_k, new_v[, new_k_scale, new_v_scale]).
    """
    b, _, _ = x.shape
    h, g, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    r = h // g
    s_max = cache_k.shape[1]
    quant = cache_k_scale is not None
    idx = jnp.broadcast_to(jnp.asarray(cache_index, jnp.int32), (b,))
    rows = jnp.arange(b)
    # masked rows scatter out of bounds -> dropped
    dest = idx if write_mask is None else jnp.where(write_mask, idx, s_max)
    pos = idx[:, None]
    q = dense_apply(params["wq"], x).reshape(b, 1, h, hd)
    k = dense_apply(params["wk"], x).reshape(b, 1, g, hd)
    v = dense_apply(params["wv"], x).reshape(b, 1, g, hd)
    if cfg.use_rope:
        q = rope(q, pos, theta=cfg.rope_theta)
        k = rope(k, pos, theta=cfg.rope_theta)
    if quant:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        kc = cache_k.at[rows, dest].set(kq[:, 0], mode="drop")
        vc = cache_v.at[rows, dest].set(vq[:, 0], mode="drop")
        ksc = cache_k_scale.at[rows, dest].set(ks[:, 0], mode="drop")
        vsc = cache_v_scale.at[rows, dest].set(vs[:, 0], mode="drop")
        kc_f = kc.astype(jnp.float32) * ksc[..., None]
        vc_f = vc.astype(jnp.float32) * vsc[..., None]
    else:
        kc = cache_k.at[rows, dest].set(k[:, 0].astype(cache_k.dtype),
                                        mode="drop")
        vc = cache_v.at[rows, dest].set(v[:, 0].astype(cache_v.dtype),
                                        mode="drop")
        kc_f = kc.astype(jnp.float32)
        vc_f = vc.astype(jnp.float32)
    kpos = jnp.arange(s_max)
    valid = kpos[None, :] <= idx[:, None]
    if cfg.window is not None:
        valid = valid & (kpos[None, :] > idx[:, None] - cfg.window)
    s = jnp.einsum("bgrd,bkgd->bgrk",
                   q.reshape(b, g, r, hd).astype(jnp.float32),
                   kc_f) / math.sqrt(hd)
    if cfg.softcap is not None:
        s = jnp.tanh(s / cfg.softcap) * cfg.softcap
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrk,bkgd->bgrd", p, vc_f)
    out = out.reshape(b, 1, h * hd).astype(x.dtype)
    y = dense_apply(params["wo"], out)
    if quant:
        return y, kc, vc, ksc, vsc
    return y, kc, vc


def prefill_attention(params, cfg: AttnConfig, x, *, cache_k, cache_v,
                      cache_index, n_valid, cache_k_scale=None,
                      cache_v_scale=None):
    """Teacher-forced chunked prefill against a decode KV cache.

    x [B, C, d]; cache_index [B] int32 (each slot's filled length);
    n_valid [B] int32 in [0, C] — how many of this slot's C columns
    carry real prompt tokens.  Rows with n_valid == 0 (slots that are
    decoding or empty) are left untouched: their writes land out of
    bounds and are dropped, and their outputs are never read.
    Returns (new_k, new_v[, new_k_scale, new_v_scale]) — prefill
    outputs are never sampled, so no logits are produced here.
    """
    b, c, _ = x.shape
    h, g, hd = cfg.n_heads, cfg.n_kv, cfg.head_dim
    r = h // g
    s_max = cache_k.shape[1]
    quant = cache_k_scale is not None
    idx = jnp.asarray(cache_index, jnp.int32)
    rows = jnp.arange(b)[:, None]
    pos = idx[:, None] + jnp.arange(c, dtype=jnp.int32)[None, :]   # [B, C]
    # columns beyond n_valid scatter out of bounds -> dropped
    dest = jnp.where(jnp.arange(c)[None, :] < n_valid[:, None], pos, s_max)
    q = dense_apply(params["wq"], x).reshape(b, c, h, hd)
    k = dense_apply(params["wk"], x).reshape(b, c, g, hd)
    v = dense_apply(params["wv"], x).reshape(b, c, g, hd)
    if cfg.use_rope:
        q = rope(q, pos, theta=cfg.rope_theta)
        k = rope(k, pos, theta=cfg.rope_theta)
    if quant:
        kq, ks = _quantize_kv(k)
        vq, vs = _quantize_kv(v)
        kc = cache_k.at[rows, dest].set(kq, mode="drop")
        vc = cache_v.at[rows, dest].set(vq, mode="drop")
        ksc = cache_k_scale.at[rows, dest].set(ks, mode="drop")
        vsc = cache_v_scale.at[rows, dest].set(vs, mode="drop")
        kc_f = kc.astype(jnp.float32) * ksc[..., None]
        vc_f = vc.astype(jnp.float32) * vsc[..., None]
    else:
        kc = cache_k.at[rows, dest].set(k.astype(cache_k.dtype), mode="drop")
        vc = cache_v.at[rows, dest].set(v.astype(cache_v.dtype), mode="drop")
        kc_f = kc.astype(jnp.float32)
        vc_f = vc.astype(jnp.float32)
    kpos = jnp.arange(s_max)
    valid = kpos[None, None, :] <= pos[:, :, None]                 # [B, C, S]
    if cfg.window is not None:
        valid = valid & (kpos[None, None, :] > pos[:, :, None] - cfg.window)
    s = jnp.einsum("bcgrd,bsgd->bgrcs",
                   q.reshape(b, c, g, r, hd).astype(jnp.float32),
                   kc_f) / math.sqrt(hd)
    if cfg.softcap is not None:
        s = jnp.tanh(s / cfg.softcap) * cfg.softcap
    s = jnp.where(valid[:, None, None, :, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrcs,bsgd->bcgrd", p, vc_f)
    out = out.reshape(b, c, h * hd).astype(x.dtype)
    y = dense_apply(params["wo"], out)
    if quant:
        return y, kc, vc, ksc, vsc
    return y, kc, vc


# ---------------------------------------------------------------------------
# gated MLP (SwiGLU / GeGLU)
# ---------------------------------------------------------------------------

def mlp_init(ini: Init, d_model: int, d_ff: int):
    return {
        "wi_gate": dense_init(ini, d_model, d_ff, ("fsdp", "tp")),
        "wi_up": dense_init(ini, d_model, d_ff, ("fsdp", "tp")),
        "wo": dense_init(ini, d_ff, d_model, ("tp", "fsdp")),
    }


def mlp_apply(params, x, *, act: str = "swiglu"):
    gate = shard_ctx.constrain(dense_apply(params["wi_gate"], x),
                               "batch", None, "tp")
    up = shard_ctx.constrain(dense_apply(params["wi_up"], x),
                             "batch", None, "tp")
    if act == "swiglu":
        a = jax.nn.silu(gate)
    elif act == "geglu":
        a = jax.nn.gelu(gate, approximate=True)
    else:
        raise ValueError(act)
    return dense_apply(params["wo"], a * up)


# ---------------------------------------------------------------------------
# Mixture of Experts (token-choice top-k, capacity dispatch, EP-sharded)
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class MoEConfig:
    d_model: int
    d_ff: int
    n_experts: int
    top_k: int
    capacity_factor: float = 1.25
    shared_expert: bool = False      # llama4-style always-on expert
    act: str = "swiglu"


def moe_init(ini: Init, cfg: MoEConfig):
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    p = {
        "router": dense_init(ini, d, e, (None, None), std=0.01),
        "wi_gate": ini.normal((e, d, f), ("ep", "fsdp", None),
                              std=1.0 / math.sqrt(d)),
        "wi_up": ini.normal((e, d, f), ("ep", "fsdp", None),
                            std=1.0 / math.sqrt(d)),
        "wo": ini.normal((e, f, d), ("ep", None, "fsdp"),
                         std=1.0 / math.sqrt(f)),
    }
    if cfg.shared_expert:
        p["shared"] = mlp_init(ini, d, f)
    return p


def moe_apply(params, cfg: MoEConfig, x):
    """x [B, S, d] -> [B, S, d].  Capacity-dropped token-choice routing."""
    b, s, d = x.shape
    t = b * s
    e, k = cfg.n_experts, cfg.top_k
    cap = max(1, int(math.ceil(t * k * cfg.capacity_factor / e)))
    xt = x.reshape(t, d)
    logits = dense_apply(params["router"],
                         xt.astype(jnp.float32))             # [T, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, k)                   # [T, k]
    top_p = top_p / jnp.maximum(top_p.sum(-1, keepdims=True), 1e-9)

    # slot position of each (token, choice) within its expert
    flat_e = top_e.reshape(-1)                               # [T*k]
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)      # [T*k, E]
    pos = jnp.cumsum(onehot, axis=0) - 1                     # [T*k, E]
    slot = jnp.take_along_axis(pos, flat_e[:, None], axis=1)[:, 0]
    keep = slot < cap

    # dispatch: [E, C, d]
    buf = jnp.zeros((e, cap, d), x.dtype)
    src = jnp.repeat(xt, k, axis=0)                          # [T*k, d]
    buf = buf.at[flat_e, jnp.where(keep, slot, cap - 1)].add(
        jnp.where(keep[:, None], src, 0), mode="drop")
    # NOTE(§Perf iter 9, REFUTED): sharding the capacity dim over the
    # batch axes made GSPMD replicate the dispatch buffer around the
    # scatter (prefill memory 17 -> 65 GiB/dev on phi3.5-moe); E-only
    # sharding is the measured optimum here.
    buf = shard_ctx.constrain(buf, "ep", None, None)

    # expert FFNs: [E, C, d] x [E, d, f]
    gate = jnp.einsum("ecd,edf->ecf", buf, mat(params["wi_gate"], x.dtype))
    up = jnp.einsum("ecd,edf->ecf", buf, mat(params["wi_up"], x.dtype))
    a = jax.nn.silu(gate) if cfg.act == "swiglu" \
        else jax.nn.gelu(gate, approximate=True)
    out_e = jnp.einsum("ecf,efd->ecd", a * up,
                       mat(params["wo"], x.dtype))           # [E, C, d]

    # combine
    gathered = out_e[flat_e, jnp.where(keep, slot, 0)]       # [T*k, d]
    gathered = jnp.where(keep[:, None], gathered, 0)
    w = top_p.reshape(-1)[:, None].astype(x.dtype)
    yt = (gathered * w).reshape(t, k, d).sum(axis=1)
    y = yt.reshape(b, s, d)
    if cfg.shared_expert:
        y = y + mlp_apply(params["shared"], x, act=cfg.act)
    # auxiliary load-balance loss (returned via side channel by caller)
    return y


def moe_aux_loss(params, cfg: MoEConfig, x):
    """Switch-style load-balance auxiliary loss."""
    t = x.shape[0] * x.shape[1]
    logits = dense_apply(params["router"],
                         x.reshape(t, -1).astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    top_e = jnp.argmax(probs, axis=-1)
    frac = jnp.mean(jax.nn.one_hot(top_e, cfg.n_experts), axis=0)
    imp = jnp.mean(probs, axis=0)
    return cfg.n_experts * jnp.sum(frac * imp)
