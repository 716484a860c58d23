"""AdamW with cosine schedule, global-norm clipping, and optional 8-bit
moment states (block-wise dynamic quantization — the paper's packing
idea applied to optimizer memory; enables 400B-scale training to fit
HBM, see configs llama4-maverick).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Any, NamedTuple, Optional

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class OptConfig:
    lr: float = 3e-4
    lr_min: float = 3e-5
    warmup: int = 100
    total_steps: int = 10000
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    clip_norm: float = 1.0
    moments_8bit: bool = False


class Q8(NamedTuple):
    """8-bit block-quantized tensor (block = last axis)."""
    q: jnp.ndarray          # int8
    scale: jnp.ndarray      # f32 [..., 1]


def _q8(x: jnp.ndarray) -> Q8:
    amax = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    # clip before the int8 cast: float division can nudge amax/scale a
    # hair past 127, and astype wraps rather than saturates
    q = jnp.clip(jnp.round(x / scale), -127, 127)
    return Q8(q.astype(jnp.int8), scale.astype(jnp.float32))


def _dq8(t: Q8) -> jnp.ndarray:
    return t.q.astype(jnp.float32) * t.scale


def schedule(cfg: OptConfig, step: jnp.ndarray) -> jnp.ndarray:
    s = step.astype(jnp.float32)
    warm = cfg.lr * s / max(1, cfg.warmup)
    prog = jnp.clip((s - cfg.warmup) / max(1, cfg.total_steps - cfg.warmup),
                    0.0, 1.0)
    cos = cfg.lr_min + 0.5 * (cfg.lr - cfg.lr_min) \
        * (1.0 + jnp.cos(math.pi * prog))
    return jnp.where(s < cfg.warmup, warm, cos)


def init(cfg: OptConfig, params: Any) -> Any:
    def zeros_like_state(p):
        # zeros_like keeps the parameter's sharding: on a mesh the
        # moments start sharded like their weights (FSDP), never whole
        # on the default device
        z = jnp.zeros_like(p, dtype=jnp.float32)
        if cfg.moments_8bit and p.ndim >= 1 and p.size >= 4096:
            return _q8(z)
        return z
    return {
        "m": jax.tree_util.tree_map(zeros_like_state, params),
        "v": jax.tree_util.tree_map(zeros_like_state, params),
        "step": jnp.zeros((), jnp.int32),
    }


def _global_norm(tree) -> jnp.ndarray:
    leaves = jax.tree_util.tree_leaves(tree)
    return jnp.sqrt(sum(jnp.sum(jnp.square(g.astype(jnp.float32)))
                        for g in leaves))


def update(cfg: OptConfig, grads: Any, state: Any, params: Any):
    """One AdamW step. Returns (new_params, new_state, metrics)."""
    step = state["step"] + 1
    gnorm = _global_norm(grads)
    clip = jnp.minimum(1.0, cfg.clip_norm / jnp.maximum(gnorm, 1e-12))
    lr = schedule(cfg, step)
    b1c = 1.0 - cfg.b1 ** step.astype(jnp.float32)
    b2c = 1.0 - cfg.b2 ** step.astype(jnp.float32)

    def upd(p, g, m, v):
        g = g.astype(jnp.float32) * clip
        m_f = _dq8(m) if isinstance(m, Q8) else m
        v_f = _dq8(v) if isinstance(v, Q8) else v
        m_f = cfg.b1 * m_f + (1.0 - cfg.b1) * g
        v_f = cfg.b2 * v_f + (1.0 - cfg.b2) * g * g
        u = (m_f / b1c) / (jnp.sqrt(v_f / b2c) + cfg.eps)
        u = u + cfg.weight_decay * p.astype(jnp.float32)
        newp = (p.astype(jnp.float32) - lr * u).astype(p.dtype)
        new_m = _q8(m_f) if isinstance(m, Q8) else m_f
        new_v = _q8(v_f) if isinstance(v, Q8) else v_f
        return newp, new_m, new_v

    is_q8 = lambda x: isinstance(x, Q8)
    flat_p, tdef = jax.tree_util.tree_flatten(params)
    flat_g = jax.tree_util.tree_flatten(grads)[0]
    flat_m = jax.tree_util.tree_flatten(state["m"], is_leaf=is_q8)[0]
    flat_v = jax.tree_util.tree_flatten(state["v"], is_leaf=is_q8)[0]
    outs = [upd(p, g, m, v)
            for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_params = jax.tree_util.tree_unflatten(tdef, [o[0] for o in outs])
    mdef = jax.tree_util.tree_structure(state["m"], is_leaf=is_q8)
    new_m = jax.tree_util.tree_unflatten(mdef, [o[1] for o in outs])
    new_v = jax.tree_util.tree_unflatten(mdef, [o[2] for o in outs])
    new_state = {"m": new_m, "v": new_v, "step": step}
    return new_params, new_state, {"grad_norm": gnorm, "lr": lr}
