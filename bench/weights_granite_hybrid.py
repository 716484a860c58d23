"""Weights of the granite-4.0-h-small configuration, made from the seed
one layer at a time, on the device.

The model's bf16 weights for one period are 7.95e9 parameters, more
than one chip holds, so nothing here makes more than one layer at once:
``layer`` makes layer ``i`` (a Mamba2 or an attention layer, by the
configuration's ``layer_types``) and ``shared`` the embedding and the
final norm.  The program packs each layer as it comes; the reference
(``reference_granite_hybrid.py``) calls the same functions again after
the program's state is freed.  The trees have the layout the program
serves (``repro.models``, family moe_hybrid); nothing of the program is
imported.

Init (the configuration's ``assumed``): kernels and the router
N(0, 1/fan_in); the depthwise conv N(0, 1/taps) with zero bias;
A_log = log U[1, 16]; dt_bias = softplus^-1 of a dt log-uniform in
[1e-3, 1e-1]; D = 1; norm scales 1; the tied embedding N(0, 0.02^2);
bf16 kernels, float32 SSM scalars and norm scales.
"""
from __future__ import annotations

import functools
import math
from typing import Dict

import jax
import jax.numpy as jnp

from weights import padded_vocab, seed_key

BF16, F32 = jnp.bfloat16, jnp.float32

# leaf ids: fixed, so adding a leaf never changes another's values
_LEAVES = {"embed": 1, "in_z": 2, "in_x": 3, "in_bc": 4, "in_dt": 5,
           "conv_w": 6, "a_log": 7, "dt_bias": 8, "out_proj": 9, "wq": 10,
           "wk": 11, "wv": 12, "wo": 13, "router": 14, "wi_gate": 15,
           "wi_up": 16, "moe_wo": 17, "shared_gate": 18, "shared_up": 19,
           "shared_wo": 20}


def sizes(cfg: Dict) -> Dict[str, int]:
    """The widths a layer is made at, from the configuration."""
    d = cfg["hidden_size"]
    di = cfg["mamba_expand"] * d
    gn = cfg["mamba_n_groups"] * cfg["mamba_d_state"]
    return {"d": d, "di": di, "gn": gn, "heads": cfg["mamba_n_heads"],
            "taps": cfg["mamba_d_conv"], "conv": di + 2 * gn,
            "h": cfg["num_attention_heads"], "kv": cfg["num_key_value_heads"],
            "hd": d // cfg["num_attention_heads"],
            "e": cfg["num_local_experts"], "f": cfg["intermediate_size"],
            "sf": cfg["shared_intermediate_size"]}


def _normal(key, leaf: str, shape, std: float):
    k = jax.random.fold_in(key, _LEAVES[leaf])
    return (jax.random.normal(k, shape, F32) * std).astype(BF16)


def _kernel(key, leaf: str, shape):
    """A kernel [..., fan_in, fan_out] drawn N(0, 1/fan_in)."""
    return {"kernel": _normal(key, leaf, shape, shape[-2] ** -0.5)}


@functools.partial(jax.jit, static_argnums=(0, 1))
def _layer(shape_key, kind, key):
    s = dict(shape_key)
    d, e, f, sf = s["d"], s["e"], s["f"], s["sf"]
    p = {"ln_mix": {"scale": jnp.ones((d,), F32)},
         "ln_mlp": {"scale": jnp.ones((d,), F32)},
         "moe": {"router": _kernel(key, "router", (d, e)),
                 "wi_gate": _normal(key, "wi_gate", (e, d, f), d ** -0.5),
                 "wi_up": _normal(key, "wi_up", (e, d, f), d ** -0.5),
                 "wo": _normal(key, "moe_wo", (e, f, d), f ** -0.5),
                 "shared": {"wi_gate": _kernel(key, "shared_gate", (d, sf)),
                            "wi_up": _kernel(key, "shared_up", (d, sf)),
                            "wo": _kernel(key, "shared_wo", (sf, d))}}}
    if kind == "mamba":
        hn = s["heads"]
        a = jax.random.uniform(jax.random.fold_in(key, _LEAVES["a_log"]),
                               (hn,), F32, 1.0, 16.0)
        dt = jnp.exp(jax.random.uniform(
            jax.random.fold_in(key, _LEAVES["dt_bias"]), (hn,), F32,
            math.log(1e-3), math.log(1e-1)))
        p["mamba"] = {
            "in_z": _kernel(key, "in_z", (d, s["di"])),
            "in_x": _kernel(key, "in_x", (d, s["di"])),
            "in_bc": _kernel(key, "in_bc", (d, 2 * s["gn"])),
            "in_dt": _kernel(key, "in_dt", (d, hn)),
            "conv": {"w": _normal(key, "conv_w", (s["conv"], s["taps"]),
                                  s["taps"] ** -0.5),
                     "b": jnp.zeros((s["conv"],), BF16)},
            "a_log": jnp.log(a),
            "d_skip": jnp.ones((hn,), F32),
            "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),    # softplus^-1
            "norm": {"scale": jnp.ones((s["di"],), F32)},
            "out_proj": _kernel(key, "out_proj", (s["di"], d)),
        }
    else:
        h, kv, hd = s["h"], s["kv"], s["hd"]
        p["attn"] = {"wq": _kernel(key, "wq", (d, h * hd)),
                     "wk": _kernel(key, "wk", (d, kv * hd)),
                     "wv": _kernel(key, "wv", (d, kv * hd)),
                     "wo": _kernel(key, "wo", (h * hd, d))}
    return p


def layer(cfg: Dict, seed: int, i: int) -> Dict:
    """Layer ``i`` (0-based within the configuration's layers)."""
    key = jax.random.fold_in(seed_key(seed), 1000 + i)
    return _layer(tuple(sorted(sizes(cfg).items())), cfg["layer_types"][i],
                  key)


@functools.partial(jax.jit, static_argnums=(0, 1))
def _shared(vocab, d, key):
    return {"embed": _normal(key, "embed", (vocab, d), 0.02),
            "ln_f": {"scale": jnp.ones((d,), F32)}}


def shared(cfg: Dict, seed: int) -> Dict:
    """The tied embedding (rows padded to a multiple of 128) and the
    final norm."""
    return _shared(padded_vocab(cfg), cfg["hidden_size"], seed_key(seed))
