"""Plain references: what the served model and the UltraNet frame
should produce, written from the configuration alone.

Nothing here imports the program.  The weights come from
``weights.py`` (the same seed, made again); the arithmetic is
straightforward ``jax.numpy``.  Each configuration states its
precision, and the reference computes in it:

* weights: symmetric per output channel at ``weight_bits``;
* activations: stored in bfloat16 (the type they are served in)
  between operations, each operation computed in float32 at
  ``highest`` matmul precision; where ``act_bits`` is below 16, every
  projection takes its input quantized symmetric per row at
  ``act_bits`` and multiplies integers exactly (every sum stays below
  2**24 in float32); at 16 the projection multiplies the bfloat16
  activations by the dequantized weights in bfloat16, accumulating in
  float32;
* the KV cache: symmetric per (position, head) at ``kv_bits``;
* the logits: float32, never rounded.

The decoder reference runs one layer at a time over a padded block of
whole sequences (prompt + served tokens), teacher-forced and causal,
and reduces the logits on the device to the few numbers the check
compares.
"""
from __future__ import annotations

import functools
import math
from typing import Dict, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import weights as W

F32, BF16 = jnp.float32, jnp.bfloat16


def _quant(x, bits: int, axis: int):
    """(integer values as float32, scale) of a symmetric quantization."""
    qmax = (1 << (bits - 1)) - 1
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.maximum(amax, 1e-8) / qmax
    return jnp.clip(jnp.round(x / scale), -qmax, qmax), scale


def _dot(a, b):
    return jnp.dot(a, b, preferred_element_type=F32)


def _linear(x, w_bf16, wbits: int, abits: Optional[int], out=BF16):
    """x [..., k] bfloat16 @ the quantized kernel [k, n] -> ``out``."""
    wq, ws = _quant(w_bf16.astype(F32), wbits, axis=0)
    if abits is not None and abits < 16:
        xq, xs = _quant(x.astype(F32), abits, axis=-1)
        return (_dot(xq, wq) * xs * ws).astype(out)
    return _dot(x, (wq * ws).astype(BF16)).astype(out)


def _rmsnorm(x, scale, eps):
    xf = x.astype(F32)
    y = xf * jax.lax.rsqrt(jnp.mean(xf * xf, -1, keepdims=True) + eps)
    return y.astype(BF16) * scale.astype(BF16)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freq = jnp.exp(-math.log(theta) * jnp.arange(half, dtype=F32) / half)
    ang = pos[:, :, None, None].astype(F32) * freq
    x1, x2 = x[..., :half].astype(F32), x[..., half:].astype(F32)
    return jnp.concatenate([x1 * jnp.cos(ang) - x2 * jnp.sin(ang),
                            x2 * jnp.cos(ang) + x1 * jnp.sin(ang)],
                           -1).astype(BF16)


def _kv(t, bits):
    q, s = _quant(t.astype(F32), bits, axis=-1)
    return q * s


@functools.partial(jax.jit, static_argnums=0)
def _layer(static, x, lp):
    (h, kv, eps, theta, wbits, abits, kvbits) = static
    with jax.default_matmul_precision("highest"):
        n, s, d = x.shape
        hd = d // h
        pos = jnp.broadcast_to(jnp.arange(s), (n, s))
        z = _rmsnorm(x, lp["ln_attn"], eps)
        q = _linear(z, lp["wq"], wbits, abits).reshape(n, s, h, hd)
        k = _linear(z, lp["wk"], wbits, abits).reshape(n, s, kv, hd)
        v = _linear(z, lp["wv"], wbits, abits).reshape(n, s, kv, hd)
        q, k = _rope(q, pos, theta), _rope(k, pos, theta)
        k, v = _kv(k, kvbits), _kv(v, kvbits)
        r = h // kv
        sc = jnp.einsum("nqgrd,nkgd->ngrqk",
                        q.reshape(n, s, kv, r, hd).astype(F32),
                        k) / math.sqrt(hd)
        causal = jnp.arange(s)[:, None] >= jnp.arange(s)[None, :]
        sc = jnp.where(causal, sc, -1e30)
        o = jnp.einsum("ngrqk,nkgd->nqgrd", jax.nn.softmax(sc, -1), v)
        x = x + _linear(o.reshape(n, s, h * hd).astype(BF16), lp["wo"],
                        wbits, abits)
        z = _rmsnorm(x, lp["ln_mlp"], eps)
        g = _linear(z, lp["wi_gate"], wbits, abits)
        u = _linear(z, lp["wi_up"], wbits, abits)
        a = jax.nn.silu(g.astype(F32)).astype(BF16) * u
        return x + _linear(a, lp["mlp_wo"], wbits, abits)


@functools.partial(jax.jit, static_argnums=0)
def _head(static, x, ln_f, head, targets, others):
    """Logits of the last norm + head, reduced per position to the best
    logit, its token, the logit of ``targets`` and of each of
    ``others``.  The head multiplies the bfloat16 activations by its
    dequantized weights (or the bfloat16 embedding, where tied)."""
    (eps, wbits, tied) = static
    z = _rmsnorm(x, ln_f, eps)
    if tied:
        logits = _dot(z, head.T)
    else:
        logits = _linear(z, head, wbits, None, out=F32)
    take = lambda t: jnp.take_along_axis(logits, t[..., None],  # noqa
                                         -1)[..., 0]
    return (logits.max(-1), logits.argmax(-1), take(targets),
            jnp.stack([take(o) for o in others]) if others else None)


def decoder_pass(cfg: Dict, seed: int, tokens: np.ndarray, *,
                 act_bits: Optional[int], targets: np.ndarray,
                 others: Sequence[np.ndarray] = (), rows: int = 4):
    """Teacher-forced forward over ``tokens`` [N, S] (padded at the
    end; causal, so padding never reaches a real position), ``rows``
    sequences at a time.  Returns, per position, (best logit, its
    token, logit of ``targets``, logits of each of ``others``), all
    [N, S] numpy arrays ([len(others), N, S] for the last)."""
    e = cfg["engine"]
    p = W.granite_params(cfg, seed)
    static = (cfg["num_attention_heads"], cfg["num_key_value_heads"],
              cfg["rms_norm_eps"], float(cfg["rope_theta"]),
              e["weight_bits"], act_bits, e["kv_bits"])
    b = p["blocks"]
    layers = [{"ln_attn": b["ln_attn"]["scale"][i],
               "ln_mlp": b["ln_mlp"]["scale"][i],
               "wq": b["attn"]["wq"]["kernel"][i],
               "wk": b["attn"]["wk"]["kernel"][i],
               "wv": b["attn"]["wv"]["kernel"][i],
               "wo": b["attn"]["wo"]["kernel"][i],
               "wi_gate": b["mlp"]["wi_gate"]["kernel"][i],
               "wi_up": b["mlp"]["wi_up"]["kernel"][i],
               "mlp_wo": b["mlp"]["wo"]["kernel"][i]}
              for i in range(cfg["num_hidden_layers"])]
    tied = bool(cfg["tie_word_embeddings"])
    head = p["embed"] if tied else p["lm_head"]
    vocab = cfg["vocab_size"]
    head = head[:vocab] if tied else head[:, :vocab]   # padded rows/cols
    parts = []
    for r in range(0, tokens.shape[0], rows):
        sl = slice(r, r + rows)
        x = p["embed"][jnp.asarray(tokens[sl])]
        for lp in layers:
            x = _layer(static, x, lp)
        parts.append([None if a is None else np.asarray(a) for a in _head(
            (cfg["rms_norm_eps"], e["weight_bits"], tied),
            x, p["ln_f"]["scale"], head, jnp.asarray(targets[sl]),
            tuple(jnp.asarray(o[sl]) for o in others))])
    del p, layers, head
    return tuple(None if parts[0][i] is None else
                 np.concatenate([q[i] for q in parts], axis=1 if i == 3
                                else 0)
                 for i in range(4))


def pad_sequences(seqs: List[Sequence[int]]) -> np.ndarray:
    """[N, S] int32, each row a whole sequence padded with 0 at the end
    (S rounded up to a multiple of 64 and N to one of 4, so few shapes
    compile; the padding rows are all 0)."""
    s = max(len(q) for q in seqs)
    s = -(-s // 64) * 64
    out = np.zeros((-(-len(seqs) // 4) * 4, s), np.int32)
    for i, q in enumerate(seqs):
        out[i, :len(q)] = q
    return out


# ---------------------------------------------------------------------------
# UltraNet
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(0, 1))
def _ultranet(static, round_to, convs, head, img):
    (act_bits, shift, pools) = static

    def conv(x, w):
        kh = w.shape[-1]
        with jax.default_matmul_precision("highest"):
            y = jax.lax.conv_general_dilated(
                x, w.astype(F32).transpose(2, 3, 1, 0), (1, 1),
                [(kh // 2, kh // 2)] * 2,
                dimension_numbers=("NHWC", "HWIO", "NHWC"))
        if round_to is not None:            # e.g. a bfloat16 result
            f = jnp.finfo(round_to)
            y = jax.lax.reduce_precision(y, exponent_bits=f.nexp,
                                         mantissa_bits=f.nmant)
        return y

    x = img.astype(F32)
    for w, pool in zip(convs, pools):
        x = jnp.clip(jnp.floor(conv(x, w) / (1 << shift)), 0,
                     (1 << act_bits) - 1)
        if pool:
            n, hh, ww, c = x.shape
            x = x.reshape(n, hh // 2, 2, ww // 2, 2, c).max(axis=(2, 4))
    return conv(x, head)


def ultranet_frames(cfg: Dict, seed: int, frames: np.ndarray, *,
                    round_to: Optional[str] = None) -> np.ndarray:
    """Head outputs [F, H/16, W/16, head] of each frame [F, 1, H, W, C]:
    integer convs (exact in float32), requantized between stages by a
    right shift clipped to ``act_bits`` unsigned, 2x2 max-pool where
    the configuration pools.  ``round_to`` rounds every conv result to
    a narrower float type (the control)."""
    convs, head = W.ultranet_weights(cfg, seed)
    static = (cfg["act_bits"], cfg["requant_shift"],
              tuple(bool(s[2]) for s in cfg["stages"]))
    rt = None if round_to is None else jnp.dtype(round_to)
    return np.stack([np.asarray(_ultranet(static, rt, convs, head,
                                          jnp.asarray(f)))[0]
                     for f in frames])
