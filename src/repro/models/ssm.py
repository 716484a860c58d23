"""Mamba2 (SSD — state-space duality) block, plus the shared short
depthwise causal conv used by both Mamba2 and RG-LRU blocks.

The short conv is the model-level site where the paper's BSEG packed
datapath applies (DESIGN.md §4): at serve time with quantized weights it
lowers onto kernels/bseg_conv1d; in training it is plain float math.

SSD follows the chunked algorithm of arXiv:2405.21060: quadratic
attention-like intra-chunk term + linear inter-chunk state recurrence,
O(S) memory, scan over chunks.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp

from .param import Init
from .layers import dense_init, dense_apply, rmsnorm_init, rmsnorm_apply


# ---------------------------------------------------------------------------
# short depthwise causal conv (taps <= 8, unrolled)
# ---------------------------------------------------------------------------

def short_conv_init(ini: Init, channels: int, taps: int):
    return {
        "w": ini.normal((channels, taps), ("tp", None),
                        std=1.0 / math.sqrt(taps)),
        "b": ini.zeros((channels,), ("tp",)),
    }


def short_conv_apply(params, x, *, state: Optional[jnp.ndarray] = None):
    """x [B, S, C].  ``state`` [B, taps-1, C] carries decode history.
    Returns (y [B, S, C], new_state).

    ``serve_params(compute="sdv")`` replaces the container with a
    ``BSEGConv`` — then the conv runs on the packed BSEG datapath.
    """
    from .quantized import BSEGConv, bseg_conv_apply
    if isinstance(params, BSEGConv):
        return bseg_conv_apply(params, x, state=state)
    taps = params["w"].shape[-1]
    if state is None:
        state = jnp.zeros((x.shape[0], taps - 1, x.shape[2]), x.dtype)
    xp = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    y = jnp.zeros_like(x)
    for q in range(taps):
        y = y + params["w"][:, q].astype(x.dtype) \
            * xp[:, q:q + x.shape[1], :]
    y = y + params["b"].astype(x.dtype)
    new_state = xp[:, xp.shape[1] - (taps - 1):, :]
    return y, new_state


# ---------------------------------------------------------------------------
# Mamba2 / SSD
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class SSMConfig:
    d_model: int
    d_inner: int            # = expand * d_model
    n_heads: int            # H ; head_dim P = d_inner // H
    d_state: int            # N
    n_groups: int = 1
    d_conv: int = 4
    chunk: int = 256
    norm_eps: float = 1e-6          # the gated RMSNorm's eps

    @property
    def head_dim(self) -> int:
        return self.d_inner // self.n_heads

    @property
    def conv_channels(self) -> int:
        return self.d_inner + 2 * self.n_groups * self.d_state


def ssm_init(ini: Init, cfg: SSMConfig):
    """Input projections are split per component (z / x / BC / dt) so
    each output dimension stays TP-divisible (the fused projection's
    2*di+2*GN+H width is not a multiple of the 16-way model axis)."""
    d, di, h = cfg.d_model, cfg.d_inner, cfg.n_heads
    gn = cfg.n_groups * cfg.d_state
    return {
        "in_z": dense_init(ini, d, di, ("fsdp", "tp")),
        "in_x": dense_init(ini, d, di, ("fsdp", "tp")),
        "in_bc": dense_init(ini, d, 2 * gn, ("fsdp", "tp")),
        "in_dt": dense_init(ini, d, h, ("fsdp", None)),
        "conv": short_conv_init(ini, di + 2 * gn, cfg.d_conv),
        "a_log": ini.zeros((h,), (None,), dtype=jnp.float32),
        "d_skip": ini.ones((h,), (None,), dtype=jnp.float32),
        "dt_bias": ini.zeros((h,), (None,), dtype=jnp.float32),
        "norm": rmsnorm_init(ini, di),
        "out_proj": dense_init(ini, di, d, ("tp", "fsdp")),
    }


def _ssd_chunked(x, dt, a, b_in, c_in, cfg: SSMConfig,
                 h0: Optional[jnp.ndarray] = None):
    """Chunked SSD scan.

    x  [B, S, H, P]; dt [B, S, H] (already softplus'ed, positive);
    a  [H] (negative);  b_in/c_in [B, S, G, N].
    Returns (y [B, S, H, P], h_final [B, H, N, P]).
    """
    bsz, s, h, p = x.shape
    g, n = b_in.shape[2], b_in.shape[3]
    q = min(cfg.chunk, s)
    assert s % q == 0, (s, q)
    nc = s // q
    rep = h // g

    # stream one chunk at a time (lax.scan): the quadratic intra-chunk
    # term only ever exists for a single chunk, so memory is O(q^2 H)
    # regardless of sequence length.
    def to_chunks(t):
        return t.reshape((bsz, nc, q) + t.shape[2:]).transpose(
            (1, 0, 2) + tuple(range(3, t.ndim + 1)))

    xc_all = to_chunks(x)                                   # [nc,B,q,H,P]
    dt_all = to_chunks(dt.astype(jnp.float32))              # [nc,B,q,H]
    bc_all = to_chunks(b_in)                                # [nc,B,q,G,N]
    cc_all = to_chunks(c_in)
    causal = jnp.tril(jnp.ones((q, q), bool))

    def scan_fn(hprev, inp):
        xc, dtc, bc, cc = inp
        da = dtc * a[None, None, :]                         # [B,q,H]
        cum = jnp.cumsum(da, axis=1)
        seg = cum[:, -1, :]                                 # [B,H]
        li = cum[:, :, None, :] - cum[:, None, :, :]        # [B,q,q,H]
        l_mat = jnp.where(causal[None, :, :, None], jnp.exp(li), 0.0)
        scores = jnp.einsum("bqgn,bkgn->bqkg", cc.astype(jnp.float32),
                            bc.astype(jnp.float32))         # [B,q,q,G]
        scores = jnp.repeat(scores, rep, axis=-1)           # [B,q,q,H]
        xdt = xc.astype(jnp.float32) * dtc[..., None]       # [B,q,H,P]
        y_intra = jnp.einsum("bqkh,bkhp->bqhp", scores * l_mat, xdt)
        ch = jnp.repeat(cc, rep, axis=2).astype(jnp.float32)
        y_inter = jnp.einsum("bqhn,bhnp->bqhp",
                             ch * jnp.exp(cum)[..., None], hprev)
        decay_state = jnp.exp(seg[:, None, :] - cum)        # [B,q,H]
        bh = jnp.repeat(bc, rep, axis=2).astype(jnp.float32)
        s_c = jnp.einsum("bqhn,bqhp->bhnp",
                         bh * decay_state[..., None], xdt)
        hnew = jnp.exp(seg)[..., None, None] * hprev + s_c
        return hnew, y_intra + y_inter

    h_init = jnp.zeros((bsz, h, n, p), jnp.float32) if h0 is None \
        else h0.astype(jnp.float32)
    hlast, ys = jax.lax.scan(scan_fn, h_init,
                             (xc_all, dt_all, bc_all, cc_all))
    y = ys.transpose(1, 0, 2, 3, 4).reshape(bsz, s, h, p)
    return y, hlast


def ssm_apply(params, cfg: SSMConfig, x, *, conv_state=None, ssm_state=None,
              decode: bool = False, n_valid=None):
    """Mamba2 block. x [B, S, d_model] -> (y, (conv_state, ssm_state)).

    ``n_valid`` [B] int32 (optional, chunked prefill from
    ``conv_state``/``ssm_state``): only the first n_valid positions of
    each row are real.  The rest take dt = 0, so they leave the SSM
    state as the last real position left it, and the conv state is
    taken from the last real positions; their outputs are never read."""
    bsz, s, _ = x.shape
    di, h, p = cfg.d_inner, cfg.n_heads, cfg.head_dim
    gn = cfg.n_groups * cfg.d_state
    z = dense_apply(params["in_z"], x)
    xin = dense_apply(params["in_x"], x)
    bc = dense_apply(params["in_bc"], x)
    dt = dense_apply(params["in_dt"], x)
    conv_in = jnp.concatenate([xin, bc], axis=-1)
    conv_prev = conv_state
    conv_out, conv_state = short_conv_apply(params["conv"], conv_in,
                                            state=conv_state)
    if n_valid is not None:
        # the inputs of the d_conv - 1 positions before the first padded one
        taps1 = conv_state.shape[1]
        hist = jnp.concatenate([conv_prev.astype(conv_in.dtype), conv_in],
                               axis=1)
        conv_state = jax.vmap(
            lambda row, n: jax.lax.dynamic_slice_in_dim(row, n, taps1)
        )(hist, jnp.asarray(n_valid, jnp.int32)).astype(conv_state.dtype)
    conv_out = jax.nn.silu(conv_out)
    xs, bs, cs = jnp.split(conv_out, [di, di + gn], axis=-1)
    xh = xs.reshape(bsz, s, h, p)
    bh = bs.reshape(bsz, s, cfg.n_groups, cfg.d_state)
    ch = cs.reshape(bsz, s, cfg.n_groups, cfg.d_state)
    dtp = jax.nn.softplus(dt.astype(jnp.float32)
                          + params["dt_bias"][None, None, :])
    if n_valid is not None:
        real = jnp.arange(s)[None, :] < jnp.asarray(n_valid)[:, None]
        dtp = jnp.where(real[..., None], dtp, 0.0)
    a = -jnp.exp(params["a_log"])                            # [H] negative

    if decode:
        # single-step recurrence: h' = exp(dt a) h + dt B x^T
        rep = h // cfg.n_groups
        dt1 = dtp[:, 0]                                      # [B,H]
        dec = jnp.exp(dt1 * a[None, :])                      # [B,H]
        bh1 = jnp.repeat(bh[:, 0], rep, axis=1)              # [B,H,N]
        ch1 = jnp.repeat(ch[:, 0], rep, axis=1)
        xdt = xh[:, 0].astype(jnp.float32) * dt1[..., None]  # [B,H,P]
        if ssm_state is None:
            ssm_state = jnp.zeros((bsz, h, cfg.d_state, p), jnp.float32)
        ssm_state = dec[..., None, None] * ssm_state \
            + jnp.einsum("bhn,bhp->bhnp", bh1.astype(jnp.float32), xdt)
        y = jnp.einsum("bhn,bhnp->bhp", ch1.astype(jnp.float32), ssm_state)
        y = y[:, None]                                       # [B,1,H,P]
    else:
        y, ssm_state = _ssd_chunked(xh, dtp, a, bh, ch, cfg, h0=ssm_state)
    y = y + params["d_skip"][None, None, :, None] \
        * xh.astype(jnp.float32)
    y = y.reshape(bsz, s, di).astype(x.dtype)
    y = rmsnorm_apply(params["norm"], y * jax.nn.silu(z), eps=cfg.norm_eps)
    return dense_apply(params["out_proj"], y), (conv_state, ssm_state)
