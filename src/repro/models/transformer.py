"""Model assembly for all assigned architecture families.

Families: dense / moe / vlm (decoder LM), encdec (encoder-decoder),
hybrid (RG-LRU + local attention, Griffin 1:2 pattern), ssm (Mamba2),
moe_hybrid (GraniteMoeHybrid: a per-layer pattern of Mamba2 and NoPE
attention mixers, every layer's FFN a dropless MoE beside a shared MLP).

Layers are stacked and driven by ``lax.scan`` (MaxText-style) so the
64-layer dry-runs stay compact in HLO; remat wraps each block.  Caches
are pytrees with one stacked leading layer axis so decode also scans.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from .param import Init, Rules
from . import shard_ctx
from . import layers as L
from . import ssm as S
from . import rglru as R


# ---------------------------------------------------------------------------
# stacked init (scan-over-layers parameter layout)
# ---------------------------------------------------------------------------

class StackedInit(Init):
    """Prepends a layer axis to every parameter."""

    def __init__(self, base: Init, n: int):
        self.base = base
        self.n = n

    def normal(self, shape, axes, **kw):
        return self.base.normal((self.n,) + tuple(shape),
                                (None,) + tuple(axes), **kw)

    def zeros(self, shape, axes, **kw):
        return self.base.zeros((self.n,) + tuple(shape),
                               (None,) + tuple(axes), **kw)

    def ones(self, shape, axes, **kw):
        return self.base.ones((self.n,) + tuple(shape),
                              (None,) + tuple(axes), **kw)

    def const(self, value, axes):
        tiled = jnp.broadcast_to(value, (self.n,) + value.shape)
        return self.base.const(tiled, (None,) + tuple(axes))


def _attn_cfg(cfg: ArchConfig, *, window=None, use_rope=True) -> L.AttnConfig:
    return L.AttnConfig(
        d_model=cfg.d_model, n_heads=cfg.n_heads, n_kv=cfg.n_kv,
        head_dim=cfg.hd, qkv_bias=cfg.qkv_bias, rope_theta=cfg.rope_theta,
        window=window, use_rope=use_rope and cfg.use_rope,
        free_qkv_sharding=cfg.free_qkv_sharding, scale=cfg.attn_scale)


def _moe_cfg(cfg: ArchConfig) -> L.MoEConfig:
    return L.MoEConfig(d_model=cfg.d_model, d_ff=cfg.d_ff,
                       n_experts=cfg.n_experts, top_k=cfg.top_k,
                       shared_expert=cfg.shared_expert, act=cfg.act,
                       shared_d_ff=cfg.shared_d_ff)


def _ssm_cfg(cfg: ArchConfig) -> S.SSMConfig:
    return S.SSMConfig(d_model=cfg.d_model, d_inner=cfg.d_inner,
                       n_heads=cfg.ssm_heads, d_state=cfg.ssm_state,
                       n_groups=cfg.ssm_groups, norm_eps=cfg.rms_eps)


#: families whose per-slot state takes ``decode_step``'s advance mask
#: and chunked prefill (``prefill_step``/``prefill_slot``)
SLOT_STATE_FAMILIES = ("dense", "moe", "vlm", "moe_hybrid")


def slot_state(cfg: ArchConfig) -> bool:
    """Whether the model's per-slot state (KV rows, SSM and conv
    state) takes the advance mask and chunked prefill; the others
    replay prompts one token per ``decode_step``."""
    return cfg.family in SLOT_STATE_FAMILIES


def _rg_cfg(cfg: ArchConfig) -> R.RGLRUConfig:
    return R.RGLRUConfig(d_model=cfg.d_model, d_rnn=cfg.d_rnn)


# ---------------------------------------------------------------------------
# block inits
# ---------------------------------------------------------------------------

def _decoder_block_init(ini: Init, cfg: ArchConfig, *, cross: bool = False):
    p = {
        "ln_attn": L.rmsnorm_init(ini, cfg.d_model),
        "attn": L.attention_init(ini, _attn_cfg(cfg)),
        "ln_mlp": L.rmsnorm_init(ini, cfg.d_model),
    }
    if cfg.family == "moe":
        p["moe"] = L.moe_init(ini, _moe_cfg(cfg))
    else:
        p["mlp"] = L.mlp_init(ini, cfg.d_model, cfg.d_ff)
    if cross:
        p["ln_cross"] = L.rmsnorm_init(ini, cfg.d_model)
        p["cross"] = L.attention_init(ini, _attn_cfg(cfg, use_rope=False))
    return p


def _hybrid_group_init(ini: Init, cfg: ArchConfig):
    """One Griffin pattern group: rec, rec, local-attn (each + MLP)."""
    def one_rec():
        return {
            "ln_mix": L.rmsnorm_init(ini, cfg.d_model),
            "rec": R.rglru_init(ini, _rg_cfg(cfg)),
            "ln_mlp": L.rmsnorm_init(ini, cfg.d_model),
            "mlp": L.mlp_init(ini, cfg.d_model, cfg.d_ff),
        }
    return {
        "rec0": one_rec(),
        "rec1": one_rec(),
        "ln_attn": L.rmsnorm_init(ini, cfg.d_model),
        "attn": L.attention_init(ini, _attn_cfg(cfg, window=cfg.window)),
        "ln_mlp": L.rmsnorm_init(ini, cfg.d_model),
        "mlp": L.mlp_init(ini, cfg.d_model, cfg.d_ff),
    }


def _hybrid_moe_layer_init(ini: Init, cfg: ArchConfig, kind: str):
    """One GraniteMoeHybrid layer: a Mamba2 or attention mixer, then
    the MoE FFN (with its shared MLP), each behind an RMSNorm."""
    p = {"ln_mix": L.rmsnorm_init(ini, cfg.d_model),
         "ln_mlp": L.rmsnorm_init(ini, cfg.d_model),
         "moe": L.moe_init(ini, _moe_cfg(cfg))}
    if kind == "mamba":
        p["mamba"] = S.ssm_init(ini, _ssm_cfg(cfg))
    else:
        p["attn"] = L.attention_init(ini, _attn_cfg(cfg))
    return p


def _ssm_block_init(ini: Init, cfg: ArchConfig):
    return {
        "ln": L.rmsnorm_init(ini, cfg.d_model),
        "ssm": S.ssm_init(ini, _ssm_cfg(cfg)),
    }


def init_params(cfg: ArchConfig, rules: Rules,
                key: Optional[jax.Array]) -> Dict[str, Any]:
    """Build the full parameter P-tree (abstract when key is None)."""
    ini = Init(key, rules, cfg.dtype)
    p: Dict[str, Any] = {
        "embed": ini.normal((cfg.vocab_padded, cfg.d_model),
                            ("tp", "fsdp"), std=0.02),
        "ln_f": L.rmsnorm_init(ini, cfg.d_model),
    }
    if not cfg.tie_embeddings:
        p["lm_head"] = ini.normal((cfg.d_model, cfg.vocab_padded),
                                  ("fsdp", "tp"), std=0.02)
    if cfg.family in ("dense", "moe", "vlm"):
        if cfg.family == "moe" and cfg.moe_every > 1:
            # homogeneous scan over (moe block + dense blocks) groups
            import dataclasses as _dc
            n_groups = cfg.n_layers // cfg.moe_every
            sini = StackedInit(ini, n_groups)
            p["blocks"] = _decoder_block_init(sini, cfg)
            dense_cfg = _dc.replace(cfg, family="dense")
            for i in range(1, cfg.moe_every):
                p[f"blocks_dense{i}"] = _decoder_block_init(sini, dense_cfg)
        else:
            sini = StackedInit(ini, cfg.n_layers)
            p["blocks"] = _decoder_block_init(sini, cfg)
    elif cfg.family == "ssm":
        sini = StackedInit(ini, cfg.n_layers)
        p["blocks"] = _ssm_block_init(sini, cfg)
    elif cfg.family == "moe_hybrid":
        # scanned over periods of the mixer pattern; "l<j>" is the
        # period's layer j, stacked over periods
        period = cfg.layer_period
        sini = StackedInit(ini, cfg.n_layers // len(period))
        p["blocks"] = {f"l{j}": _hybrid_moe_layer_init(sini, cfg, kind)
                       for j, kind in enumerate(period)}
    elif cfg.family == "hybrid":
        n_groups = cfg.n_layers // 3
        n_tail = cfg.n_layers - 3 * n_groups      # trailing rec layers
        sini = StackedInit(ini, n_groups)
        p["groups"] = _hybrid_group_init(sini, cfg)
        if n_tail:
            tini = StackedInit(ini, n_tail)
            p["tail"] = {
                "ln_mix": L.rmsnorm_init(tini, cfg.d_model),
                "rec": R.rglru_init(tini, _rg_cfg(cfg)),
                "ln_mlp": L.rmsnorm_init(tini, cfg.d_model),
                "mlp": L.mlp_init(tini, cfg.d_model, cfg.d_ff),
            }
    elif cfg.family == "encdec":
        eini = StackedInit(ini, cfg.n_enc_layers)
        dini = StackedInit(ini, cfg.n_dec_layers)
        p["enc_blocks"] = _decoder_block_init(eini, cfg)
        p["dec_blocks"] = _decoder_block_init(dini, cfg, cross=True)
        p["ln_enc"] = L.rmsnorm_init(ini, cfg.d_model)
    else:
        raise ValueError(cfg.family)
    if cfg.frontend == "vision":
        p["proj_patches"] = L.dense_init(ini, cfg.d_model, cfg.d_model,
                                         (None, None))
    return p


# ---------------------------------------------------------------------------
# block applies
# ---------------------------------------------------------------------------

def _maybe_remat(fn, cfg: ArchConfig):
    return jax.checkpoint(fn) if cfg.remat else fn


def _layer_loop(cfg: ArchConfig, body, x, stacked, n: int,
                allow_group: bool = False):
    """lax.scan over stacked layer params, or an unrolled python loop
    (cfg.scan_layers=False) so cost_analysis sees every layer's FLOPs —
    XLA's cost model counts while-loop bodies exactly once.

    cfg.remat_group > 1 enables sqrt-L checkpointing: an outer scan over
    layer *groups* whose bodies are rematerialized wholesale, so only
    n/group layer-boundary activations are saved instead of n (§Perf
    iteration for the memory roofline term)."""
    g = cfg.remat_group
    if allow_group and cfg.scan_layers and g > 1 and n % g == 0 and n > g:
        grouped = jax.tree_util.tree_map(
            lambda a: a.reshape((n // g, g) + a.shape[1:]), stacked)

        def outer(xc, sl):
            def run_group(xx):
                return jax.lax.scan(body, xx, sl)[0]
            return jax.checkpoint(run_group)(xc), None

        x, _ = jax.lax.scan(outer, x, grouped)
        return x, None
    if cfg.scan_layers:
        x, ys = jax.lax.scan(body, x, stacked)
        return x, ys
    ys = []
    for i in range(n):
        sl = jax.tree_util.tree_map(lambda a: a[i], stacked)
        x, y = body(x, sl)
        ys.append(y)
    if all(y is None for y in ys):
        return x, None
    return x, jax.tree_util.tree_map(lambda *a: jnp.stack(a), *ys)


def _decoder_block_apply(bp, cfg: ArchConfig, x, positions, *,
                         cross_kv=None, causal=True, diff=True):
    acfg = _attn_cfg(cfg, window=cfg.window if cfg.family == "dense"
                     else None)
    h, _ = L.attention_apply(bp["attn"], acfg,
                             L.rmsnorm_apply(bp["ln_attn"], x),
                             positions=positions, causal=causal,
                             chunk=cfg.attn_chunk, differentiable=diff)
    x = x + h
    if cross_kv is not None:
        ccfg = _attn_cfg(cfg, use_rope=False)
        h, _ = L.attention_apply(bp["cross"], ccfg,
                                 L.rmsnorm_apply(bp["ln_cross"], x),
                                 positions=positions, kv=cross_kv,
                                 causal=False, chunk=cfg.attn_chunk,
                                 differentiable=diff)
        x = x + h
    y = L.rmsnorm_apply(bp["ln_mlp"], x)
    if cfg.family == "moe" and diff:        # training: capacity dispatch
        x = x + L.moe_apply(bp["moe"], _moe_cfg(cfg), y)
    elif cfg.family == "moe":               # inference: dropless
        x = x + L.moe_dropless(bp["moe"], _moe_cfg(cfg), y)[0]
    else:
        x = x + L.mlp_apply(bp["mlp"], y, act=cfg.act)
    return x


def _rec_layer_apply(rp, cfg: ArchConfig, x, *, conv_state=None,
                     rnn_state=None):
    h, states = R.rglru_apply(rp["rec"], _rg_cfg(cfg),
                              L.rmsnorm_apply(rp["ln_mix"], x),
                              conv_state=conv_state, rnn_state=rnn_state)
    x = x + h
    x = x + L.mlp_apply(rp["mlp"], L.rmsnorm_apply(rp["ln_mlp"], x),
                        act=cfg.act)
    return x, states


def _hybrid_group_apply(gp, cfg: ArchConfig, x, positions, *, states=None,
                        diff=True):
    st = states or {}
    x, s0 = _rec_layer_apply(gp["rec0"], cfg, x,
                             conv_state=st.get("conv0"),
                             rnn_state=st.get("rnn0"))
    x, s1 = _rec_layer_apply(gp["rec1"], cfg, x,
                             conv_state=st.get("conv1"),
                             rnn_state=st.get("rnn1"))
    acfg = _attn_cfg(cfg, window=cfg.window)
    h, _ = L.attention_apply(gp["attn"], acfg,
                             L.rmsnorm_apply(gp["ln_attn"], x),
                             positions=positions, causal=True,
                             chunk=cfg.attn_chunk, differentiable=diff)
    x = x + h
    x = x + L.mlp_apply(gp["mlp"], L.rmsnorm_apply(gp["ln_mlp"], x),
                        act=cfg.act)
    new_states = {"conv0": s0[0], "rnn0": s0[1],
                  "conv1": s1[0], "rnn1": s1[1]}
    return x, new_states


def _hybrid_moe_layer(bp, cfg: ArchConfig, x, mixer, *, dropless=True):
    """One GraniteMoeHybrid layer around ``mixer`` (normed input ->
    (output, new mixer state)):

        h = x + rm * mixer(RMSNorm(x));  y = h + rm * MoE(RMSNorm(h))

    with rm the residual multiplier and MoE the routed experts plus
    the shared MLP.  ``dropless=False`` is the training path (capacity
    dispatch).  Returns (y, mixer state, routing stats or None)."""
    rm, eps = cfg.residual_multiplier, cfg.rms_eps
    h, state = mixer(L.rmsnorm_apply(bp["ln_mix"], x, eps=eps))
    x = x + h * rm
    z = L.rmsnorm_apply(bp["ln_mlp"], x, eps=eps)
    if dropless:
        y, stats = L.moe_dropless(bp["moe"], _moe_cfg(cfg), z)
    else:
        y, stats = L.moe_apply(bp["moe"], _moe_cfg(cfg), z), None
    return x + y * rm, state, stats


def _hybrid_moe_period(bp, cfg: ArchConfig, x, positions, *, diff=True):
    """Full-sequence forward of one period of the mixer pattern (no
    cache): the training path when ``diff``, else dropless."""
    acfg, scfg = _attn_cfg(cfg), _ssm_cfg(cfg)
    for j, kind in enumerate(cfg.layer_period):
        if kind == "mamba":
            def mixer(a, _p=bp[f"l{j}"]["mamba"]):
                return S.ssm_apply(_p, scfg, a)
        else:
            def mixer(a, _p=bp[f"l{j}"]["attn"]):
                return L.attention_apply(
                    _p, acfg, a, positions=positions, causal=True,
                    chunk=cfg.attn_chunk, differentiable=diff)
        x, _, _ = _hybrid_moe_layer(bp[f"l{j}"], cfg, x, mixer,
                                    dropless=not diff)
    return x


def _hybrid_moe_cached(cfg: ArchConfig, params, cache, x, *, decode,
                       advance=None, n_valid=None):
    """The period scan of ``decode_step`` (``decode``: x [B, 1, d],
    ``advance`` [B] or None) and of chunked prefill (x [B, C, d],
    ``n_valid`` [B]) against the cache of ``init_cache``: attention
    layers keep int8 KV rows, Mamba2 layers conv and SSM state, all
    laid out (layers, B, ...).  A slot whose advance is 0 (decode) or
    whose n_valid is 0 (prefill) keeps every state bit-identical.
    Returns (x, new cache leaves without ``index``, stats [L, 3])."""
    period = cfg.layer_period
    n_per = cfg.n_layers // len(period)
    mam = [j for j, t in enumerate(period) if t == "mamba"]
    att = [j for j, t in enumerate(period) if t != "mamba"]
    b = x.shape[0]
    index = jnp.broadcast_to(jnp.asarray(cache["index"], jnp.int32), (b,))
    acfg, scfg = _attn_cfg(cfg), _ssm_cfg(cfg)
    if decode:
        keep = None if advance is None else jnp.asarray(advance) > 0
        wmask = keep
    else:
        keep = jnp.asarray(n_valid, jnp.int32) > 0

    def per_period(name, n):
        if not n:
            return None
        leaf = cache[name]
        return leaf.reshape((n_per, n) + leaf.shape[1:])

    kv_names = tuple(nm for nm in ("k", "v", "k_scale", "v_scale")
                     if nm in cache)
    kv = tuple(per_period(nm, len(att)) for nm in kv_names)
    conv, ssm = per_period("conv", len(mam)), per_period("ssm", len(mam))

    def hold(new, old):
        if keep is None:
            return new
        return jnp.where(keep.reshape((b,) + (1,) * (new.ndim - 1)),
                         new, old)

    def body(xc, sl):
        bp, kvs, cv, st = sl
        new_kv = [[] for _ in kv_names]
        new_cv, new_st, stats = [], [], []
        for j, kind in enumerate(period):
            lp = bp[f"l{j}"]
            if kind == "mamba":
                i = mam.index(j)

                def mixer(a, _p=lp["mamba"], _i=i):
                    h, (c1, s1) = S.ssm_apply(
                        _p, scfg, a, conv_state=cv[_i], ssm_state=st[_i],
                        decode=decode, n_valid=None if decode else n_valid)
                    return h, (hold(c1, cv[_i]), hold(s1, st[_i]))
            else:
                i = att.index(j)
                kvi = [t[i] for t in kvs]
                ck, cvv = kvi[0], kvi[1]
                ks, vs = kvi[2:] if len(kvi) == 4 else (None, None)

                def mixer(a, _p=lp["attn"], ck=ck, cvv=cvv, ks=ks, vs=vs):
                    if decode:
                        out = L.decode_attention(
                            _p, acfg, a, cache_k=ck, cache_v=cvv,
                            cache_index=index, cache_k_scale=ks,
                            cache_v_scale=vs, write_mask=wmask)
                    else:
                        out = L.prefill_attention(
                            _p, acfg, a, cache_k=ck, cache_v=cvv,
                            cache_index=index, n_valid=n_valid,
                            cache_k_scale=ks, cache_v_scale=vs)
                    return out[0], out[1:]
            xc, state, st_j = _hybrid_moe_layer(lp, cfg, xc, mixer)
            stats.append(st_j)
            if kind == "mamba":
                new_cv.append(state[0])
                new_st.append(state[1])
            else:
                for lst, t in zip(new_kv, state):
                    lst.append(t)
        stack = lambda ts: jnp.stack(ts) if ts else None  # noqa: E731
        return xc, (tuple(stack(t) for t in new_kv), stack(new_cv),
                    stack(new_st), jnp.stack(stats))

    x, (nkv, ncv, nst, stats) = _layer_loop(
        cfg, body, x, (params["blocks"], kv, conv, ssm), n_per)
    new = {nm: t.reshape(cache[nm].shape) for nm, t in
           zip(kv_names + ("conv", "ssm"), nkv + (ncv, nst))
           if t is not None}
    return x, new, stats.reshape(cfg.n_layers, len(L.MOE_STATS))


# ---------------------------------------------------------------------------
# full forward (train / prefill)
# ---------------------------------------------------------------------------

def _embed(cfg: ArchConfig, params, tokens):
    x = params["embed"][tokens]
    if cfg.act == "geglu":                 # gemma family scales embeddings
        x = x * math.sqrt(cfg.d_model)
    if cfg.embedding_multiplier != 1.0:    # granite
        x = x * cfg.embedding_multiplier
    return shard_ctx.constrain(x.astype(cfg.dtype), "batch", None, None)


def _finish(cfg: ArchConfig, params, x, mode: str):
    if mode == "hidden":
        return L.rmsnorm_apply(params["ln_f"], x, eps=cfg.rms_eps)
    if mode == "last_logits":
        return _unembed(cfg, params, x[:, -1:, :])
    return _unembed(cfg, params, x)


def unembed_hidden(cfg: ArchConfig, params, h):
    """Project already-normed hidden states to logits (chunked loss)."""
    if cfg.tie_embeddings:
        logits = h @ params["embed"].astype(h.dtype).T
    elif hasattr(params["lm_head"], "qat_apply"):
        logits = params["lm_head"].qat_apply(h)   # QAT STE (train/qat)
    else:
        logits = h @ L.mat(params["lm_head"], h.dtype)
    return _scale_logits(cfg, logits)


def _scale_logits(cfg: ArchConfig, logits):
    logits = logits.astype(jnp.float32)
    if cfg.logits_scaling != 1.0:          # granite divides its logits
        logits = logits / cfg.logits_scaling
    return shard_ctx.constrain(logits, "batch", None, "tp")


def _unembed(cfg: ArchConfig, params, x):
    x = L.rmsnorm_apply(params["ln_f"], x, eps=cfg.rms_eps)
    if cfg.tie_embeddings:
        logits = x @ params["embed"].astype(x.dtype).T
    elif hasattr(params["lm_head"], "qat_apply"):
        logits = params["lm_head"].qat_apply(x)   # QAT STE (train/qat)
    else:
        logits = x @ L.mat(params["lm_head"], x.dtype)
    return _scale_logits(cfg, logits)


def forward(cfg: ArchConfig, params, batch: Dict[str, jnp.ndarray],
            *, collect_kv: bool = False, diff: bool = True,
            mode: str = "logits"):
    """mode: "logits" (full [B,S,V]), "hidden" (post-ln_f states, for
    the memory-safe chunked loss), "last_logits" (serving prefill —
    only the next-token logits are ever needed).  ``diff=False`` is an
    inference forward: MoE layers run dropless there, as in serving;
    the training forward keeps capacity dispatch."""
    """Full-sequence forward.  batch:
      dense/moe/ssm/hybrid: {"tokens": [B, S]}
      vlm:    {"tokens": [B, S - n_patches], "patches": [B, n_patches, d]}
      encdec: {"src": [B, S_src, d], "tokens": [B, S_tgt]}
    Returns logits [B, S_out, vocab] (and optionally stacked kv).
    """
    if cfg.family == "encdec":
        return _forward_encdec(cfg, params, batch, collect_kv=collect_kv,
                               diff=diff, mode=mode)
    tokens = batch["tokens"]
    x = _embed(cfg, params, tokens)
    if cfg.family == "vlm":
        patches = L.dense_apply(params["proj_patches"],
                                batch["patches"].astype(cfg.dtype))
        x = jnp.concatenate([patches, x], axis=1)
    b, s_tot, _ = x.shape
    positions = jnp.broadcast_to(jnp.arange(s_tot, dtype=jnp.int32),
                                 (b, s_tot))

    if cfg.family in ("dense", "moe", "vlm"):
        me = cfg.moe_every if cfg.family == "moe" else 1
        if me > 1:
            import dataclasses as _dc
            dense_cfg = _dc.replace(cfg, family="dense")
            n_groups = cfg.n_layers // me

            def body(xc, bps):
                def blk(xx):
                    xx = _decoder_block_apply(bps[0], cfg, xx, positions,
                                              diff=diff)
                    for i in range(1, me):
                        xx = _decoder_block_apply(bps[i], dense_cfg, xx,
                                                  positions, diff=diff)
                    return xx
                return _maybe_remat(blk, cfg)(xc), None

            xs = tuple([params["blocks"]]
                       + [params[f"blocks_dense{i}"] for i in range(1, me)])
            x, _ = _layer_loop(cfg, body, x, xs, n_groups,
                               allow_group=True)
        else:
            def body(xc, bp):
                return _maybe_remat(
                    lambda xx: _decoder_block_apply(bp, cfg, xx, positions,
                                                    diff=diff),
                    cfg)(xc), None
            x, _ = _layer_loop(cfg, body, x, params["blocks"], cfg.n_layers,
                           allow_group=True)
    elif cfg.family == "ssm":
        def body(xc, bp):
            def blk(xx):
                h, _ = S.ssm_apply(bp["ssm"], _ssm_cfg(cfg),
                                   L.rmsnorm_apply(bp["ln"], xx))
                return xx + h
            return _maybe_remat(blk, cfg)(xc), None
        x, _ = _layer_loop(cfg, body, x, params["blocks"], cfg.n_layers,
                           allow_group=True)
    elif cfg.family == "moe_hybrid":
        def body(xc, bp):
            return _maybe_remat(
                lambda xx: _hybrid_moe_period(bp, cfg, xx, positions,
                                              diff=diff), cfg)(xc), None
        x, _ = _layer_loop(cfg, body, x, params["blocks"],
                           cfg.n_layers // len(cfg.layer_period),
                           allow_group=True)
    elif cfg.family == "hybrid":
        def body(xc, gp):
            def blk(xx):
                y, _ = _hybrid_group_apply(gp, cfg, xx, positions,
                                           diff=diff)
                return y
            return _maybe_remat(blk, cfg)(xc), None
        x, _ = _layer_loop(cfg, body, x, params["groups"],
                           cfg.n_layers // 3, allow_group=True)
        if "tail" in params:
            def tbody(xc, tp):
                def blk(xx):
                    y, _ = _rec_layer_apply(tp, cfg, xx)
                    return y
                return _maybe_remat(blk, cfg)(xc), None
            x, _ = _layer_loop(cfg, tbody, x, params["tail"],
                               cfg.n_layers - 3 * (cfg.n_layers // 3))
    else:
        raise ValueError(cfg.family)
    return _finish(cfg, params, x, mode)


def _forward_encdec(cfg: ArchConfig, params, batch, *, collect_kv=False,
                    diff=True, mode: str = "logits"):
    src = batch["src"].astype(cfg.dtype)      # precomputed frame embeds
    b = src.shape[0]
    pos_src = jnp.broadcast_to(
        jnp.arange(src.shape[1], dtype=jnp.int32), (b, src.shape[1]))

    def enc_body(xc, bp):
        return _maybe_remat(
            lambda xx: _decoder_block_apply(bp, cfg, xx, pos_src,
                                            causal=False, diff=diff),
            cfg)(xc), None
    enc, _ = _layer_loop(cfg, enc_body, src, params["enc_blocks"],
                         cfg.n_enc_layers, allow_group=True)
    enc = L.rmsnorm_apply(params["ln_enc"], enc)

    x = _embed(cfg, params, batch["tokens"])
    pos = jnp.broadcast_to(jnp.arange(x.shape[1], dtype=jnp.int32),
                           (b, x.shape[1]))

    def dec_body(xc, bp):
        return _maybe_remat(
            lambda xx: _decoder_block_apply(bp, cfg, xx, pos,
                                            cross_kv=(enc, enc), diff=diff),
            cfg)(xc), None
    x, _ = _layer_loop(cfg, dec_body, x, params["dec_blocks"],
                       cfg.n_dec_layers, allow_group=True)
    return _finish(cfg, params, x, mode)


# ---------------------------------------------------------------------------
# decode (single new token against caches)
# ---------------------------------------------------------------------------

def init_cache(cfg: ArchConfig, rules: Rules, batch_size: int, s_max: int,
               *, abstract: bool = False):
    """Build the decode cache P-tree (stacked layer axis first).

    The TP axis lands on whichever KV-cache dim it divides: the KV-head
    dim when n_kv is a TP multiple, else the head_dim (smaller GQA/MQA
    archs) — so a 16-wide model axis always shards the 32k caches."""
    ini = Init(None if abstract else jax.random.PRNGKey(0), rules, cfg.dtype)
    b = batch_size
    hd, kv = cfg.hd, cfg.n_kv
    tp = max(1, rules.tp_degree)
    kv_ax = ("tp", None) if kv and kv % tp == 0 else         ((None, "tp") if hd and hd % tp == 0 else (None, None))

    kv8 = cfg.serve_kv_bits == 8 and cfg.family in SLOT_STATE_FAMILIES
    kv_dtype = jnp.int8 if kv8 else cfg.dtype

    def kvc(n_layers, s):
        out = {
            "k": ini.zeros((n_layers, b, s, kv, hd),
                           (None, "batch", None) + kv_ax, dtype=kv_dtype),
            "v": ini.zeros((n_layers, b, s, kv, hd),
                           (None, "batch", None) + kv_ax, dtype=kv_dtype),
        }
        if kv8:
            out["k_scale"] = ini.zeros(
                (n_layers, b, s, kv), (None, "batch", None, kv_ax[0]),
                dtype=jnp.float32)
            out["v_scale"] = ini.zeros(
                (n_layers, b, s, kv), (None, "batch", None, kv_ax[0]),
                dtype=jnp.float32)
        return out

    # per-slot decode positions: slot i has index[i] valid cache entries,
    # so a freed slot can be reset to 0 and rejoined mid-wave while its
    # neighbours keep decoding (token-level continuous batching).
    cache: Dict[str, Any] = {"index": ini.zeros((b,), (None,),
                                                dtype=jnp.int32)}
    if cfg.family in ("dense", "moe", "vlm"):
        cache.update(kvc(cfg.n_layers, s_max))
    elif cfg.family == "encdec":
        cache.update(kvc(cfg.n_dec_layers, s_max))
        cache["cross_k"] = ini.zeros(
            (cfg.n_dec_layers, b, s_max, kv, hd),
            (None, "batch", None) + kv_ax)
        cache["cross_v"] = ini.zeros(
            (cfg.n_dec_layers, b, s_max, kv, hd),
            (None, "batch", None) + kv_ax)
    elif cfg.family in ("ssm", "moe_hybrid"):
        n_mamba = cfg.n_layers
        if cfg.family == "moe_hybrid":
            # attention layers keep KV rows, Mamba2 layers conv and SSM
            # state; each kind stacked in layer order
            n_mamba = sum(t == "mamba" for t in cfg.layer_types)
            if n_mamba < cfg.n_layers:
                cache.update(kvc(cfg.n_layers - n_mamba, s_max))
        scfg = _ssm_cfg(cfg)
        if n_mamba:
            cache["conv"] = ini.zeros(
                (n_mamba, b, scfg.d_conv - 1, scfg.conv_channels),
                (None, "batch", None, "tp"))
            nh = scfg.n_heads
            h_ax = ("tp", None, None) if nh % tp == 0 \
                else (None, "tp", None)
            cache["ssm"] = ini.zeros(
                (n_mamba, b, nh, scfg.d_state, scfg.head_dim),
                (None, "batch") + h_ax, dtype=jnp.float32)
    elif cfg.family == "hybrid":
        n_groups = cfg.n_layers // 3
        n_tail = cfg.n_layers - 3 * n_groups
        w = min(cfg.window, s_max)
        cache["k"] = ini.zeros((n_groups, b, w, kv, hd),
                               (None, "batch", None) + kv_ax)
        cache["v"] = ini.zeros((n_groups, b, w, kv, hd),
                               (None, "batch", None) + kv_ax)
        for pref, n in (("g", n_groups), ("t", n_tail)):
            reps = 2 if pref == "g" else 1
            for r in range(reps):
                cache[f"{pref}_conv{r}"] = ini.zeros(
                    (n, b, 3, cfg.d_rnn), (None, "batch", None, "tp"))
                cache[f"{pref}_rnn{r}"] = ini.zeros(
                    (n, b, cfg.d_rnn), (None, "batch", "tp"),
                    dtype=jnp.float32)
    return cache


def _decode_attn_ring(bp, cfg: ArchConfig, x, k_cache, v_cache, index,
                      *, window: int):
    """Sliding-window decode with a ring buffer of size ``window``.

    ``index`` is the per-slot position vector [B]: each batch slot has
    its own ring write head and entry ages."""
    acfg = _attn_cfg(cfg, window=window)
    b = x.shape[0]
    h, g, hd = cfg.n_heads, cfg.n_kv, cfg.hd
    index = jnp.broadcast_to(jnp.asarray(index, jnp.int32), (b,))
    slot = jnp.mod(index, window)                                  # [B]
    pos = index[:, None]
    q = L.dense_apply(bp["wq"], x).reshape(b, 1, h, hd)
    k = L.dense_apply(bp["wk"], x).reshape(b, 1, g, hd)
    v = L.dense_apply(bp["wv"], x).reshape(b, 1, g, hd)
    q = L.rope(q, pos, theta=cfg.rope_theta)
    k = L.rope(k, pos, theta=cfg.rope_theta)
    rows = jnp.arange(b)
    kc = k_cache.at[rows, slot].set(k[:, 0].astype(k_cache.dtype))
    vc = v_cache.at[rows, slot].set(v[:, 0].astype(v_cache.dtype))
    # entry ages: slot s holds position index - ((slot - s) mod window)
    offs = jnp.mod(slot[:, None] - jnp.arange(window)[None, :], window)
    entry_pos = index[:, None] - offs                              # [B, W]
    valid = (entry_pos >= 0) & (entry_pos >= index[:, None] - window + 1)
    r = h // g
    s = jnp.einsum("bgrd,bkgd->bgrk",
                   q.reshape(b, g, r, hd).astype(jnp.float32),
                   kc.astype(jnp.float32)) / math.sqrt(hd)
    s = jnp.where(valid[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bgrk,bkgd->bgrd", p, vc.astype(jnp.float32))
    out = out.reshape(b, 1, h * hd).astype(x.dtype)
    return L.dense_apply(bp["wo"], out), kc, vc


def decode_step(cfg: ArchConfig, params, cache, tokens: jnp.ndarray,
                advance=None, *, moe_stats: bool = False):
    """One decode step.  tokens [B, 1] int32; returns (logits, new cache).

    The cache pytree layout matches ``init_cache`` (stacked layer axis);
    the layer loop is a ``lax.scan`` carrying x and scanning cache
    slices alongside parameters.  ``cache["index"]`` is the per-slot
    position vector [B] (scalars from legacy snapshots broadcast); the
    new cache always carries the normalized [B] form so the pytree
    signature stays stable under jit.

    ``advance`` [B] int32 (optional, ``slot_state`` families only):
    slots with 0 neither write their state (KV rows, SSM and conv
    state) nor move their index — they are mid-prefill in a mixed
    continuous-batching iteration and their logits are discarded.
    Omitted means every slot advances (the classic step).

    ``moe_stats=True`` returns (logits, new cache, stats): the MoE
    layers' routing statistics, int32 [n_moe_layers, 3] in the order
    of ``layers.MOE_STATS`` (None for a model without MoE layers).
    MoE layers run dropless here.
    """
    b = tokens.shape[0]
    index = jnp.broadcast_to(jnp.asarray(cache["index"], jnp.int32), (b,))
    if advance is not None and not slot_state(cfg):
        raise ValueError(
            f"advance mask unsupported for family {cfg.family!r}")
    x = _embed(cfg, params, tokens)
    stats = None

    if cfg.family in ("dense", "moe", "vlm"):
        acfg = _attn_cfg(cfg)
        me = cfg.moe_every if cfg.family == "moe" else 1
        if advance is None:
            bump, wmask = 1, None
        else:
            bump = jnp.broadcast_to(jnp.asarray(advance, jnp.int32), (b,))
            wmask = bump > 0

        kv8 = "k_scale" in cache

        def one(bp, xc, kc, vc, moe: bool, ks=None, vs=None):
            outs = L.decode_attention(
                bp["attn"], acfg, L.rmsnorm_apply(bp["ln_attn"], xc),
                cache_k=kc, cache_v=vc, cache_index=index,
                cache_k_scale=ks, cache_v_scale=vs, write_mask=wmask)
            h, rest = outs[0], outs[1:]
            y = xc + h
            z = L.rmsnorm_apply(bp["ln_mlp"], y)
            st = None
            if moe:
                m, st = L.moe_dropless(bp["moe"], _moe_cfg(cfg), z)
                y = y + m
            else:
                y = y + L.mlp_apply(bp["mlp"], z, act=cfg.act)
            return (y,) + rest + (st,)

        if me > 1:
            n_groups = cfg.n_layers // me
            kg = cache["k"].reshape((n_groups, me) + cache["k"].shape[1:])
            vg = cache["v"].reshape((n_groups, me) + cache["v"].shape[1:])

            def body(xc, sl):
                bps, kc, vc = sl[:-2], sl[-2], sl[-1]
                nks, nvs = [], []
                y = xc
                for i in range(me):
                    outs = one(bps[i], y, kc[i], vc[i], moe=(i == 0))
                    y, nk, nv = outs[:3]
                    if i == 0:
                        st = outs[-1]
                    nks.append(nk)
                    nvs.append(nv)
                return y, (jnp.stack(nks), jnp.stack(nvs), st)

            xs = tuple([params["blocks"]]
                       + [params[f"blocks_dense{i}"] for i in range(1, me)]
                       + [kg, vg])
            x, (nk, nv, stats) = _layer_loop(cfg, body, x, xs, n_groups)
            nk = nk.reshape(cache["k"].shape)
            nv = nv.reshape(cache["v"].shape)
            new_cache = dict(cache, k=nk, v=nv, index=index + bump)
        elif kv8:
            def body(xc, sl):
                bp, kc, vc, ks, vs = sl
                y, nk, nv, nks, nvs, st = one(bp, xc, kc, vc,
                                              moe=(cfg.family == "moe"),
                                              ks=ks, vs=vs)
                return y, (nk, nv, nks, nvs, st)

            x, (nk, nv, nks, nvs, stats) = _layer_loop(
                cfg, body, x, (params["blocks"], cache["k"], cache["v"],
                               cache["k_scale"], cache["v_scale"]),
                cfg.n_layers)
            new_cache = dict(cache, k=nk, v=nv, k_scale=nks, v_scale=nvs,
                             index=index + bump)
        else:
            def body(xc, sl):
                bp, kc, vc = sl
                y, nk, nv, st = one(bp, xc, kc, vc,
                                    moe=(cfg.family == "moe"))
                return y, (nk, nv, st)

            x, (nk, nv, stats) = _layer_loop(
                cfg, body, x, (params["blocks"], cache["k"], cache["v"]),
                cfg.n_layers)
            new_cache = dict(cache, k=nk, v=nv, index=index + bump)

    elif cfg.family == "moe_hybrid":
        x, new, stats = _hybrid_moe_cached(cfg, params, cache, x,
                                           decode=True, advance=advance)
        bump = 1 if advance is None else \
            jnp.broadcast_to(jnp.asarray(advance, jnp.int32), (b,))
        new_cache = dict(cache, **new, index=index + bump)

    elif cfg.family == "encdec":
        acfg = _attn_cfg(cfg)
        ccfg = _attn_cfg(cfg, use_rope=False)

        def body(xc, sl):
            bp, kc, vc, ck, cv = sl
            h, nk, nv = L.decode_attention(
                bp["attn"], acfg, L.rmsnorm_apply(bp["ln_attn"], xc),
                cache_k=kc, cache_v=vc, cache_index=index)
            y = xc + h
            # cross attention against the precomputed encoder cache
            b = y.shape[0]
            g, r, hd = cfg.n_kv, cfg.n_heads // cfg.n_kv, cfg.hd
            q = L.dense_apply(bp["cross"]["wq"],
                              L.rmsnorm_apply(bp["ln_cross"], y))
            q = q.reshape(b, g, r, hd).astype(jnp.float32)
            s = jnp.einsum("bgrd,bkgd->bgrk", q,
                           ck.astype(jnp.float32)) / math.sqrt(hd)
            pr = jax.nn.softmax(s, axis=-1)
            o = jnp.einsum("bgrk,bkgd->bgrd", pr, cv.astype(jnp.float32))
            o = o.reshape(b, 1, cfg.n_heads * hd).astype(y.dtype)
            y = y + L.dense_apply(bp["cross"]["wo"], o)
            z = L.rmsnorm_apply(bp["ln_mlp"], y)
            y = y + L.mlp_apply(bp["mlp"], z, act=cfg.act)
            return y, (nk, nv)

        x, (nk, nv) = _layer_loop(
            cfg, body, x, (params["dec_blocks"], cache["k"],
                           cache["v"], cache["cross_k"], cache["cross_v"]),
            cfg.n_dec_layers)
        new_cache = dict(cache, k=nk, v=nv, index=index + 1)

    elif cfg.family == "ssm":
        scfg = _ssm_cfg(cfg)

        def body(xc, sl):
            bp, conv, ssm_st = sl
            h, (nconv, nssm) = S.ssm_apply(
                bp["ssm"], scfg, L.rmsnorm_apply(bp["ln"], xc),
                conv_state=conv, ssm_state=ssm_st, decode=True)
            return xc + h, (nconv, nssm)

        x, (nconv, nssm) = _layer_loop(
            cfg, body, x, (params["blocks"], cache["conv"], cache["ssm"]),
            cfg.n_layers)
        new_cache = dict(cache, conv=nconv, ssm=nssm, index=index + 1)

    elif cfg.family == "hybrid":
        w = cache["k"].shape[2]

        def body(xc, sl):
            gp, kc, vc, c0, r0, c1, r1 = sl
            y, s0 = _rec_layer_apply(gp["rec0"], cfg, xc,
                                     conv_state=c0, rnn_state=r0)
            y, s1 = _rec_layer_apply(gp["rec1"], cfg, y,
                                     conv_state=c1, rnn_state=r1)
            h, nk, nv = _decode_attn_ring(
                gp["attn"], cfg, L.rmsnorm_apply(gp["ln_attn"], y),
                kc, vc, index, window=w)
            y = y + h
            y = y + L.mlp_apply(gp["mlp"], L.rmsnorm_apply(gp["ln_mlp"], y),
                                act=cfg.act)
            return y, (nk, nv, s0[0], s0[1], s1[0], s1[1])

        x, outs = _layer_loop(
            cfg, body, x, (params["groups"], cache["k"], cache["v"],
                           cache["g_conv0"], cache["g_rnn0"],
                           cache["g_conv1"], cache["g_rnn1"]),
            cfg.n_layers // 3)
        new_cache = dict(cache, k=outs[0], v=outs[1],
                         g_conv0=outs[2], g_rnn0=outs[3],
                         g_conv1=outs[4], g_rnn1=outs[5],
                         index=index + 1)
        if "tail" in params:
            def tbody(xc, sl):
                tp, c0, r0 = sl
                y, s0 = _rec_layer_apply(tp, cfg, xc,
                                         conv_state=c0, rnn_state=r0)
                return y, (s0[0], s0[1])
            x, touts = _layer_loop(
                cfg, tbody, x, (params["tail"], cache["t_conv0"],
                                cache["t_rnn0"]),
                cfg.n_layers - 3 * (cfg.n_layers // 3))
            new_cache.update(t_conv0=touts[0], t_rnn0=touts[1])
    else:
        raise ValueError(cfg.family)

    if moe_stats:
        return _unembed(cfg, params, x), new_cache, stats
    return _unembed(cfg, params, x), new_cache


def reset_slot(cache, slot):
    """Zero batch slot ``slot`` across every cache leaf.

    Leaves are laid out (layers, B, ...); ``index`` is the per-slot
    position vector [B].  Clearing the position plus all per-slot
    state (KV rows, quant scales, ring buffers, conv/SSM/RNN state)
    is what makes a freed slot safe to hand to a new session mid-wave:
    only positions <= index[slot] are ever attended, and each position
    is rewritten before it becomes attendable, so no stale state from
    the previous occupant can leak into the new one.
    """
    out = {}
    for name, leaf in cache.items():
        if name == "index":
            out[name] = leaf.at[slot].set(0)
        else:
            out[name] = leaf.at[:, slot].set(jnp.zeros((), leaf.dtype))
    return out


def _prefill_forward(cfg: ArchConfig, params, cache, tokens: jnp.ndarray,
                     n_valid: jnp.ndarray):
    """Shared chunked teacher-forcing core for the ``slot_state``
    families: returns (final hidden states [B, C, d], new cache, MoE
    routing stats or None).  ``prefill_step`` discards the hidden
    states (cache-only prompt replay); ``verify_step`` unembeds them
    (speculative verification needs the logits at every fed
    position)."""
    if not slot_state(cfg):
        raise ValueError(f"prefill_step: unsupported family {cfg.family}")
    b = tokens.shape[0]
    index = jnp.broadcast_to(jnp.asarray(cache["index"], jnp.int32), (b,))
    n_valid = jnp.asarray(n_valid, jnp.int32)
    x = _embed(cfg, params, tokens)
    if cfg.family == "moe_hybrid":
        x, new, stats = _hybrid_moe_cached(cfg, params, cache, x,
                                           decode=False, n_valid=n_valid)
        return x, dict(cache, **new, index=index + n_valid), stats
    acfg = _attn_cfg(cfg)          # same attention config as decode_step
    me = cfg.moe_every if cfg.family == "moe" else 1
    kv8 = "k_scale" in cache

    def one(bp, xc, kc, vc, moe: bool, ks=None, vs=None):
        outs = L.prefill_attention(
            bp["attn"], acfg, L.rmsnorm_apply(bp["ln_attn"], xc),
            cache_k=kc, cache_v=vc, cache_index=index, n_valid=n_valid,
            cache_k_scale=ks, cache_v_scale=vs)
        h, rest = outs[0], outs[1:]
        y = xc + h
        z = L.rmsnorm_apply(bp["ln_mlp"], y)
        st = None
        if moe:
            m, st = L.moe_dropless(bp["moe"], _moe_cfg(cfg), z)
            y = y + m
        else:
            y = y + L.mlp_apply(bp["mlp"], z, act=cfg.act)
        return (y,) + rest + (st,)

    if me > 1:
        n_groups = cfg.n_layers // me
        kg = cache["k"].reshape((n_groups, me) + cache["k"].shape[1:])
        vg = cache["v"].reshape((n_groups, me) + cache["v"].shape[1:])

        def body(xc, sl):
            bps, kc, vc = sl[:-2], sl[-2], sl[-1]
            nks, nvs = [], []
            y = xc
            for i in range(me):
                outs = one(bps[i], y, kc[i], vc[i], moe=(i == 0))
                y, nk, nv = outs[:3]
                if i == 0:
                    st = outs[-1]
                nks.append(nk)
                nvs.append(nv)
            return y, (jnp.stack(nks), jnp.stack(nvs), st)

        xs = tuple([params["blocks"]]
                   + [params[f"blocks_dense{i}"] for i in range(1, me)]
                   + [kg, vg])
        x, (nk, nv, stats) = _layer_loop(cfg, body, x, xs, n_groups)
        nk = nk.reshape(cache["k"].shape)
        nv = nv.reshape(cache["v"].shape)
        return x, dict(cache, k=nk, v=nv, index=index + n_valid), stats
    if kv8:
        def body(xc, sl):
            bp, kc, vc, ks, vs = sl
            y, nk, nv, nks, nvs, st = one(bp, xc, kc, vc,
                                          moe=(cfg.family == "moe"),
                                          ks=ks, vs=vs)
            return y, (nk, nv, nks, nvs, st)

        x, (nk, nv, nks, nvs, stats) = _layer_loop(
            cfg, body, x, (params["blocks"], cache["k"], cache["v"],
                           cache["k_scale"], cache["v_scale"]),
            cfg.n_layers)
        return x, dict(cache, k=nk, v=nv, k_scale=nks, v_scale=nvs,
                       index=index + n_valid), stats

    def body(xc, sl):
        bp, kc, vc = sl
        y, nk, nv, st = one(bp, xc, kc, vc, moe=(cfg.family == "moe"))
        return y, (nk, nv, st)

    x, (nk, nv, stats) = _layer_loop(
        cfg, body, x, (params["blocks"], cache["k"], cache["v"]),
        cfg.n_layers)
    return x, dict(cache, k=nk, v=nv, index=index + n_valid), stats


def prefill_step(cfg: ArchConfig, params, cache, tokens: jnp.ndarray,
                 n_valid: jnp.ndarray, *, moe_stats: bool = False):
    """One chunked-prefill step for the ``slot_state`` families.

    tokens [B, C] int32 — a teacher-forced prompt chunk per slot,
    zero-padded; n_valid [B] int32 in [0, C] says how many columns of
    each row are real.  Slots with n_valid == 0 (decoding or empty)
    are untouched: their writes drop out of bounds and their index
    does not advance.  A long prompt therefore stalls a wave of
    decoders for ceil(P/C) iterations instead of P.  Returns the new
    cache only — prefill logits are never sampled.

    The moe_hybrid family prefills its Mamba2 layers through the
    chunked SSD scan from the slot's state (padded columns take dt = 0
    and leave the state as the last real column left it).  The other
    recurrent families (ssm/hybrid) and encdec replay prompts one
    token per ``decode_step`` instead (chunk = 1).

    ``moe_stats=True`` returns (new cache, MoE routing stats or None)
    as ``decode_step`` does.
    """
    _, new_cache, stats = _prefill_forward(cfg, params, cache, tokens,
                                           n_valid)
    return (new_cache, stats) if moe_stats else new_cache


def verify_step(cfg: ArchConfig, params, cache, tokens: jnp.ndarray,
                n_valid: jnp.ndarray):
    """Logit-returning chunked teacher-forcing: the speculative
    verification wave (DESIGN.md §5.2).

    tokens [B, C] int32 — per slot, the pending token followed by the
    draft's proposals; n_valid [B] int32 in [0, C] (0 freezes a slot
    exactly as in ``prefill_step``).  Returns (logits [B, C, vocab],
    new cache): column j holds the next-token logits after consuming
    tokens[:, :j+1].

    The hidden state at a fed position is computed by the SAME layer
    stack chunked prefill already runs (prefill attention writes KV at
    ``index + j`` and attends ``kpos <= index + j`` — the decode
    step's causal semantics per column), so column j's logits are
    bit-identical to the logits a sequential ``decode_step`` over the
    same tokens would produce.  Greedy acceptance against these logits
    is therefore *exact*: a speculative completion equals the
    non-speculative one token for token.  Columns at or beyond a
    slot's ``n_valid`` return garbage logits (their KV writes drop out
    of bounds) — callers only read accepted prefixes.
    """
    x, new_cache, _ = _prefill_forward(cfg, params, cache, tokens, n_valid)
    return _unembed(cfg, params, x), new_cache


def verify_slot(cfg: ArchConfig, params, cache, slot,
                tokens: jnp.ndarray, n_valid: jnp.ndarray):
    """``verify_step`` over a SINGLE batch slot (the ``prefill_slot``
    of verification: one compiled [1, C] program serves every slot).
    tokens [1, C] int32; n_valid [1] int32.  Returns (logits
    [1, C, vocab], new cache with only ``slot``'s column updated)."""
    slot = jnp.asarray(slot, jnp.int32)
    sub = {name: jax.lax.dynamic_slice_in_dim(
        leaf, slot, 1, axis=0 if name == "index" else 1)
        for name, leaf in cache.items()}
    logits, new = verify_step(cfg, params, sub, tokens, n_valid)
    merged = {name: jax.lax.dynamic_update_slice_in_dim(
        cache[name], new[name], slot, axis=0 if name == "index" else 1)
        for name in cache}
    return logits, merged


def rollback_slot(cache, slot, n):
    """Rewind batch slot ``slot`` by ``n`` positions (clamped at 0).

    This is the whole rejection path of speculative decoding: the
    per-slot position vector ``index[B]`` is decremented and *nothing
    else is touched*.  KV columns past the new index hold the rejected
    drafts' keys/values, but the decode/prefill validity mask only
    attends ``kpos <= index`` and every position is rewritten (an
    in-bounds ``.at[...].set``) before it becomes attendable again —
    the same staleness argument that makes ``reset_slot`` + slot reuse
    sound, so a rollback is a pure index decrement.  ``slot`` and
    ``n`` may be traced (one compiled program serves every slot).
    """
    slot = jnp.asarray(slot, jnp.int32)
    n = jnp.asarray(n, jnp.int32)
    index = jnp.asarray(cache["index"], jnp.int32)
    return dict(cache,
                index=index.at[slot].set(
                    jnp.maximum(index[slot] - n, 0)))


def prefill_slot(cfg: ArchConfig, params, cache, slot,
                 tokens: jnp.ndarray, n_valid: jnp.ndarray, *,
                 moe_stats: bool = False):
    """Chunked prefill of a SINGLE batch slot.

    ``slot`` is a traced int32 scalar (one compiled program serves
    every slot); tokens [1, C] int32; n_valid [1] int32.  The slot's
    row of every cache leaf is sliced out, prefilled as a batch of
    one via ``prefill_step``, and scattered back.  Prefill is
    per-slot by construction, so a request's prompt replay runs the
    exact same compiled program — on the same single-row operands —
    whether it opens a wave or joins one mid-flight: bit-exactness
    across wave compositions is structural.  ``moe_stats=True``
    returns (new cache, MoE routing stats or None).
    """
    slot = jnp.asarray(slot, jnp.int32)
    sub = {name: jax.lax.dynamic_slice_in_dim(
        leaf, slot, 1, axis=0 if name == "index" else 1)
        for name, leaf in cache.items()}
    new, stats = prefill_step(cfg, params, sub, tokens, n_valid,
                              moe_stats=True)
    merged = {name: jax.lax.dynamic_update_slice_in_dim(
        cache[name], new[name], slot, axis=0 if name == "index" else 1)
        for name in cache}
    return (merged, stats) if moe_stats else merged
