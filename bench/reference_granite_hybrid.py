"""Plain reference of the granite-4.0-h-small configuration: what the
served model should produce, written from the configuration alone.

Nothing here imports the program.  The weights come from
``weights_granite_hybrid.py`` (the same seed, made again one layer at a
time); the arithmetic is straightforward ``jax.numpy`` in float32 at
``highest`` matmul precision:

* weights: symmetric per output channel (per expert and output channel
  for the expert banks) at ``weight_bits``, dequantized to float32; the
  router, the depthwise conv and the norms as made;
* activations: float32 throughout; where ``act_bits`` is below 16 (the
  control), every projection takes its input quantized symmetric per
  row at ``act_bits``;
* the attention layer's keys and values: symmetric per (position,
  head) at ``kv_bits``;
* Mamba2: the sequential recurrence, one position at a time;
* the MoE: each token's top-k experts by router logit, weighted by the
  softmax of those k logits; each expert runs on the rows routed to it
  (a gather per expert), plus the shared MLP on every row;
* the logits: float32, divided by ``logits_scaling``.

It runs one layer at a time over a padded block of whole sequences
(prompt + served tokens), teacher-forced and causal, and reduces the
logits on the device to the few numbers the check compares, as
``reference.decoder_pass`` does for the dense decoder.
"""
from __future__ import annotations

import functools
from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

import reference
import weights_granite_hybrid as W

F32 = jnp.float32
#: rows per block through the mixers, positions per block of the head,
#: queries per block of the attention scores, and the granularity the
#: rows routed to one expert are padded to (few shapes compile)
ROWS, HEAD_POS, Q_BLOCK, EXPERT_ROWS = 4, 256, 64, 512


def _deq(w, bits: int):
    """A kernel [..., k, n] quantized per output channel, as float32."""
    q, s = reference._quant(w.astype(F32), bits, axis=-2)
    return q * s


def _lin(x, w, abits: Optional[int]):
    """x [..., k] float32 @ the dequantized kernel [k, n]."""
    if abits is not None and abits < 16:
        q, s = reference._quant(x, abits, axis=-1)
        x = q * s
    return jnp.dot(x, w, preferred_element_type=F32)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) \
        * scale.astype(F32)


def _silu(x):
    return x * jax.nn.sigmoid(x)


@functools.partial(jax.jit, static_argnums=0)
def _mamba(static, x, lp, w):
    """x [n, S, d] -> x after one Mamba2 mixer and its residual."""
    (heads, dstate, groups, eps, rm, abits) = static
    with jax.default_matmul_precision("highest"):
        n, s, _ = x.shape
        a = _rms(x, lp["ln_mix"]["scale"], eps)
        m = lp["mamba"]
        z = _lin(a, w["in_z"], abits)
        xbc = jnp.concatenate([_lin(a, w["in_x"], abits),
                               _lin(a, w["in_bc"], abits)], -1)
        dt = _lin(a, w["in_dt"], abits)
        cw, cb = m["conv"]["w"].astype(F32), m["conv"]["b"].astype(F32)
        taps = cw.shape[1]
        pad = jnp.pad(xbc, ((0, 0), (taps - 1, 0), (0, 0)))
        conv = sum(cw[:, q] * pad[:, q:q + s] for q in range(taps)) + cb
        conv = _silu(conv)
        di = z.shape[-1]
        hp = di // heads
        xs = conv[..., :di].reshape(n, s, heads, hp)
        bs = conv[..., di:di + groups * dstate].reshape(n, s, groups, dstate)
        cs = conv[..., di + groups * dstate:].reshape(n, s, groups, dstate)
        rep = heads // groups
        bs, cs = jnp.repeat(bs, rep, 2), jnp.repeat(cs, rep, 2)
        dt = jax.nn.softplus(dt + m["dt_bias"])                 # [n, S, H]
        a_neg = -jnp.exp(m["a_log"])

        def step(h, inp):
            xt, bt, ct, dtt = inp       # [n,H,P], [n,H,N], [n,H,N], [n,H]
            h = jnp.exp(dtt * a_neg)[..., None, None] * h \
                + (dtt[..., None] * bt)[..., None] * xt[:, :, None, :]
            return h, jnp.einsum("bhn,bhnp->bhp", ct, h)

        seq = lambda t: jnp.moveaxis(t, 1, 0)                   # noqa: E731
        h0 = jnp.zeros((n, heads, dstate, hp), F32)
        _, y = jax.lax.scan(step, h0, (seq(xs), seq(bs), seq(cs), seq(dt)))
        y = jnp.moveaxis(y, 0, 1) + m["d_skip"][:, None] * xs
        y = _rms(y.reshape(n, s, di) * _silu(z), m["norm"]["scale"], eps)
        return x + rm * _lin(y, w["out_proj"], abits)


@functools.partial(jax.jit, static_argnums=0)
def _attention(static, x, lp, w):
    """x [n, S, d] -> x after causal NoPE attention and its residual."""
    (h, kv, scale, eps, rm, abits, kvbits) = static
    with jax.default_matmul_precision("highest"):
        n, s, d = x.shape
        hd = w["wq"].shape[1] // h
        a = _rms(x, lp["ln_mix"]["scale"], eps)
        q = _lin(a, w["wq"], abits).reshape(n, s, kv, h // kv, hd)
        k = reference._kv(_lin(a, w["wk"], abits).reshape(n, s, kv, hd),
                          kvbits)
        v = reference._kv(_lin(a, w["wv"], abits).reshape(n, s, kv, hd),
                          kvbits)
        kpos = jnp.arange(s)

        def block(q0):
            qb = jax.lax.dynamic_slice_in_dim(q, q0, Q_BLOCK, axis=1)
            sc = jnp.einsum("nqgrd,nkgd->ngrqk", qb, k) * scale
            causal = (q0 + jnp.arange(Q_BLOCK))[:, None] >= kpos[None, :]
            sc = jnp.where(causal, sc, -1e30)
            return jnp.einsum("ngrqk,nkgd->nqgrd", jax.nn.softmax(sc, -1), v)

        starts = jnp.arange(0, s, Q_BLOCK)
        o = jax.lax.map(block, starts)              # [B, n, Q, g, r, hd]
        o = jnp.moveaxis(o, 0, 1).reshape(n, s, h * hd)
        return x + rm * _lin(o, w["wo"], abits)


@functools.partial(jax.jit, static_argnums=0)
def _route(static, x, lp, w):
    """The MoE input of every row, its top-k experts and their weights,
    and the shared MLP's output."""
    (k, eps, abits) = static
    with jax.default_matmul_precision("highest"):
        b = _rms(x, lp["ln_mlp"]["scale"], eps)
        logits = jnp.dot(b, lp["moe"]["router"]["kernel"].astype(F32))
        top_l, top_e = jax.lax.top_k(logits, k)
        g = _lin(b, w["shared_gate"], abits)
        u = _lin(b, w["shared_up"], abits)
        shared = _lin(_silu(g) * u, w["shared_wo"], abits)
        return b, top_e, jax.nn.softmax(top_l, -1), shared


@functools.partial(jax.jit, static_argnums=0, donate_argnums=1)
def _expert(abits, y, b, rows, wts, wg, wu, wo):
    """y += wts * expert(b[rows]) at ``rows`` (padding rows weigh 0)."""
    with jax.default_matmul_precision("highest"):
        xb = b[rows]
        hid = _silu(_lin(xb, wg, abits)) * _lin(xb, wu, abits)
        return y.at[rows].add(wts[:, None] * _lin(hid, wo, abits))


def _moe(cfg: Dict, x, lp, w, abits):
    """x [T, d] -> x after the MoE block and its residual."""
    b, top_e, top_w, shared = _route(
        (cfg["num_experts_per_tok"], cfg["rms_norm_eps"], abits), x, lp, w)
    top_e, top_w = np.asarray(top_e), np.asarray(top_w)
    y = jnp.zeros_like(x)
    for e in range(cfg["num_local_experts"]):
        tok, slot = np.nonzero(top_e == e)
        if not len(tok):
            continue
        m = -(-len(tok) // EXPERT_ROWS) * EXPERT_ROWS
        rows = np.zeros(m, np.int32)
        rows[:len(tok)] = tok
        wts = np.zeros(m, np.float32)
        wts[:len(tok)] = top_w[tok, slot]
        y = _expert(abits, y, b, jnp.asarray(rows), jnp.asarray(wts),
                    w["wi_gate"][e], w["wi_up"][e], w["moe_wo"][e])
    return x + cfg["residual_multiplier"] * (y + shared)


def _dequantized(lp, bits: int) -> Dict:
    """The layer's projection kernels, quantized and dequantized."""
    out = {}
    mix = lp.get("mamba") or lp["attn"]
    for name in ("in_z", "in_x", "in_bc", "in_dt", "out_proj", "wq", "wk",
                 "wv", "wo"):
        if name in mix:
            out[name] = _deq(mix[name]["kernel"], bits)
    moe = lp["moe"]
    out.update(wi_gate=_deq(moe["wi_gate"], bits),
               wi_up=_deq(moe["wi_up"], bits), moe_wo=_deq(moe["wo"], bits),
               shared_gate=_deq(moe["shared"]["wi_gate"]["kernel"], bits),
               shared_up=_deq(moe["shared"]["wi_up"]["kernel"], bits),
               shared_wo=_deq(moe["shared"]["wo"]["kernel"], bits))
    return out


@functools.partial(jax.jit, static_argnums=0)
def _head(static, x, ln_f, emb, targets, others, probe):
    (eps, scaling) = static
    with jax.default_matmul_precision("highest"):
        logits = jnp.dot(_rms(x, ln_f, eps), emb.astype(F32).T) / scaling
    take = lambda t: jnp.take_along_axis(logits, t[..., None],  # noqa
                                         -1)[..., 0]
    return (logits.max(-1), logits.argmax(-1), take(targets),
            jnp.stack([take(o) for o in others]) if others else None,
            None if probe is None else logits[..., probe])


def hybrid_pass(cfg: Dict, seed: int, tokens: np.ndarray,
                lengths: Sequence[int], *, act_bits: Optional[int],
                targets: np.ndarray, others: Sequence[np.ndarray] = (),
                probe: Optional[np.ndarray] = None):
    """Teacher-forced forward over ``tokens`` [N, S] (row i real up to
    ``lengths[i]``, padded after; causal, so padding never reaches a
    real position).  Returns, per position, (best logit, its token,
    logit of ``targets``, logits of each of ``others``, logits at the
    vocabulary entries ``probe`` [m]), numpy arrays [N, S]
    ([len(others), N, S] and [N, S, m] for the last two; None where
    not asked for)."""
    e = cfg["engine"]
    eps, rm = cfg["rms_norm_eps"], cfg["residual_multiplier"]
    n, s = tokens.shape
    sh = W.shared(cfg, seed)
    blocks = [sh["embed"][jnp.asarray(tokens[r:r + ROWS])].astype(F32)
              * cfg["embedding_multiplier"] for r in range(0, n, ROWS)]
    lens = np.zeros(n, np.int64)
    lens[:len(lengths)] = lengths
    real = np.arange(s)[None, :] < lens[:, None]
    flat = np.flatnonzero(real.reshape(-1))          # real (row, pos)
    d = cfg["hidden_size"]
    for i, kind in enumerate(cfg["layer_types"]):
        lp = W.layer(cfg, seed, i)
        w = _dequantized(lp, e["weight_bits"])
        if kind == "mamba":
            static = (cfg["mamba_n_heads"], cfg["mamba_d_state"],
                      cfg["mamba_n_groups"], eps, rm, act_bits)
            blocks = [_mamba(static, x, lp, w) for x in blocks]
        else:
            static = (cfg["num_attention_heads"],
                      cfg["num_key_value_heads"],
                      float(cfg["attention_multiplier"]), eps, rm, act_bits,
                      e["kv_bits"])
            blocks = [_attention(static, x, lp, w) for x in blocks]
        x = jnp.concatenate(blocks).reshape(n * s, d)
        xr = _moe(cfg, x[jnp.asarray(flat)], lp, w, act_bits)
        x = x.at[jnp.asarray(flat)].set(xr).reshape(n, s, d)
        blocks = [x[r:r + ROWS] for r in range(0, n, ROWS)]
        del lp, w, x, xr
    probe = None if probe is None else jnp.asarray(probe, jnp.int32)
    # per block of rows and of positions: best, argmax, at targets
    # ([rows, pos]), at others ([k, rows, pos]), at probe ([rows, pos, m])
    pos_axis = (1, 1, 1, 2, 1)
    parts = []
    for r, x in zip(range(0, n, ROWS), blocks):
        row = []
        for p in range(0, s, HEAD_POS):
            sl = (slice(r, r + ROWS), slice(p, p + HEAD_POS))
            row.append([None if a is None else np.asarray(a) for a in _head(
                (eps, float(cfg["logits_scaling"])), x[:, p:p + HEAD_POS],
                sh["ln_f"]["scale"], sh["embed"][:cfg["vocab_size"]],
                jnp.asarray(targets[sl]),
                tuple(jnp.asarray(o[sl]) for o in others), probe)])
        parts.append([None if row[0][i] is None else np.concatenate(
            [q[i] for q in row], axis=pos_axis[i]) for i in range(5)])
    return tuple(None if parts[0][i] is None else
                 np.concatenate([q[i] for q in parts],
                                axis=pos_axis[i] - 1 if i == 3 else 0)
                 for i in range(5))
