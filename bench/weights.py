"""Weights made from the seed, on the device, in one jitted call each.

The benchmark makes the weights and hands them to the program; the
reference (``reference.py``) calls the same functions again after the
program's state is freed, so it takes nothing the program has made.
The seed only enters as data (a key array), so the compiled programs
are the same for every seed and come from the compile cache.
"""
from __future__ import annotations

import functools
from typing import Dict

import jax
import jax.numpy as jnp


def seed_key(seed: int) -> jax.Array:
    """A key for any non-negative seed below 2**62 (the driver's seeds
    exceed 32 bits)."""
    if not 0 <= seed < 1 << 62:
        raise ValueError(f"seed {seed} outside [0, 2**62)")
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, seed & 0x7FFFFFFF)
    return jax.random.fold_in(k, (seed >> 31) & 0x7FFFFFFF)


# leaf ids: fixed, so adding a leaf never changes another's values
_LEAVES = {"embed": 1, "lm_head": 2, "wq": 3, "wk": 4, "wv": 5, "wo": 6,
           "wi_gate": 7, "wi_up": 8, "mlp_wo": 9}


def _normal(key, leaf: str, shape, std: float, layers: int = 0):
    k = jax.random.fold_in(key, _LEAVES[leaf])
    if not layers:
        return (jax.random.normal(k, shape, jnp.float32) * std
                ).astype(jnp.bfloat16)
    keys = jax.vmap(lambda i: jax.random.fold_in(k, i))(jnp.arange(layers))
    return jax.vmap(lambda kk: (jax.random.normal(kk, shape, jnp.float32)
                                * std).astype(jnp.bfloat16))(keys)


def padded_vocab(cfg: Dict) -> int:
    return -(-cfg["vocab_size"] // 128) * 128


@functools.partial(jax.jit, static_argnums=0)
def _granite(shape_key, key):
    (layers, d, ff, h, kv, vocab, tied) = shape_key
    hd = d // h
    std_d, std_ff = d ** -0.5, ff ** -0.5
    ones = jnp.ones((layers, d), jnp.float32)
    p = {
        "embed": _normal(key, "embed", (vocab, d), 0.02),
        "ln_f": {"scale": jnp.ones((d,), jnp.float32)},
        "blocks": {
            "ln_attn": {"scale": ones},
            "attn": {
                "wq": {"kernel": _normal(key, "wq", (d, h * hd), std_d,
                                         layers)},
                "wk": {"kernel": _normal(key, "wk", (d, kv * hd), std_d,
                                         layers)},
                "wv": {"kernel": _normal(key, "wv", (d, kv * hd), std_d,
                                         layers)},
                "wo": {"kernel": _normal(key, "wo", (h * hd, d),
                                         (h * hd) ** -0.5, layers)},
            },
            "ln_mlp": {"scale": ones},
            "mlp": {
                "wi_gate": {"kernel": _normal(key, "wi_gate", (d, ff),
                                              std_d, layers)},
                "wi_up": {"kernel": _normal(key, "wi_up", (d, ff), std_d,
                                            layers)},
                "wo": {"kernel": _normal(key, "mlp_wo", (ff, d), std_ff,
                                         layers)},
            },
        },
    }
    if not tied:
        p["lm_head"] = _normal(key, "lm_head", (d, vocab), 0.02)
    return p


def granite_params(cfg: Dict, seed: int) -> Dict:
    """The decoder's parameter tree (the layout ``repro.models``
    serves: stacked ``blocks``, bf16 kernels, f32 norm scales)."""
    shape_key = (cfg["num_hidden_layers"], cfg["hidden_size"],
                 cfg["intermediate_size"], cfg["num_attention_heads"],
                 cfg["num_key_value_heads"], padded_vocab(cfg),
                 bool(cfg["tie_word_embeddings"]))
    return _granite(shape_key, seed_key(seed))


@functools.partial(jax.jit, static_argnums=0)
def _ultranet(layers, key):
    out = []
    for i, (shape, mean, std) in enumerate(layers):
        w = jax.random.normal(jax.random.fold_in(key, i), shape) * std + mean
        out.append(jnp.clip(jnp.round(w), -8, 7).astype(jnp.int8))
    return out


#: the activations the UltraNet weights are drawn for (unsigned 4-bit,
#: as a trained network's thresholds keep them): mean and rms of a
#: stage's input, and of the frame's pixels
ACT_MEAN, ACT_RMS = 6.0, 7.0
PIXEL_MEAN, PIXEL_RMS = 7.5, 8.8


def ultranet_weights(cfg: Dict, seed: int):
    """Signed ``weight_bits``-wide kernels [C_out, C_in, k, k] (int8
    containers): the 3x3 stages, then the 1x1 head.

    Uniform random int4 kernels would let every activation die within
    four stages (the requantizing shift leaves a zero accumulator
    mean), and a check of all-zero outputs checks nothing.  So each
    layer's kernel is drawn around a mean and spread that keep its
    accumulator at about ``ACT_MEAN`` requantized steps, with a spread
    of 4 steps, given its fan-in: a stand-in for trained weights."""
    if cfg["weight_bits"] != 4:
        raise ValueError("the UltraNet weights are drawn as int4")
    step = float(1 << cfg["requant_shift"])
    layers, cin, m, r = [], cfg["in_channels"], PIXEL_MEAN, PIXEL_RMS
    for cout, ksize, _ in cfg["stages"]:
        fan = cin * ksize * ksize
        layers.append(((cout, cin, ksize, ksize), ACT_MEAN * step / (fan * m),
                       4 * step / (fan ** 0.5 * r)))
        cin, m, r = cout, ACT_MEAN, ACT_RMS
    layers.append(((cfg["head_channels"], cin, 1, 1),
                   ACT_MEAN * step / (cin * m), 4 * step / (cin ** 0.5 * r)))
    ws = _ultranet(tuple(layers), seed_key(seed))
    return ws[:-1], ws[-1]
