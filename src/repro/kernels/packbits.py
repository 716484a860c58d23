"""Lane pack/unpack kernel: dense w-bit integers <-> int32 words.

This is the HBM storage layout used by the packed execution modes:
``32 // w`` consecutive elements of the minor axis share one int32 word
(two's-complement fields, sign handled on unpack).  The kernel is a
bandwidth op — one VMEM pass, shifts and masks only.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl


def _unpack_body(w: int, packed_ref, out_ref):
    per = 32 // w
    word = packed_ref[...]                       # [bm, bn] int32
    parts = []
    for i in range(per):
        f = (word >> (i * w)) & ((1 << w) - 1)
        # sign-extend the w-bit field:
        f = jnp.where(f >= (1 << (w - 1)), f - (1 << w), f)
        parts.append(f.astype(jnp.int8))
    out_ref[...] = jnp.stack(parts, axis=-1).reshape(out_ref.shape)


def _pack_body(w: int, vals_ref, out_ref):
    per = 32 // w
    bm, bn = out_ref.shape
    vals = vals_ref[...].astype(jnp.int32).reshape(bm, bn, per)
    word = jnp.zeros((bm, bn), jnp.int32)
    for i in range(per):
        field = vals[..., i] & ((1 << w) - 1)
        word = word | (field << (i * w))
    out_ref[...] = word


@functools.partial(jax.jit, static_argnames=("w", "block", "interpret"))
def unpack_words(packed: jnp.ndarray, *, w: int, interpret: bool,
                 block: int = 256) -> jnp.ndarray:
    """int32 [m, n_words] -> int8 [m, n_words * (32//w)] (sign-extended)."""
    m, nw = packed.shape
    per = 32 // w
    bm = min(8, m)
    bn = min(block, nw)
    grid = (pl.cdiv(m, bm), pl.cdiv(nw, bn))
    return pl.pallas_call(
        functools.partial(_unpack_body, w),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn * per), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, nw * per), jnp.int8),
        interpret=interpret,
    )(packed)


@functools.partial(jax.jit, static_argnames=("w", "block", "interpret"))
def pack_words(vals: jnp.ndarray, *, w: int, interpret: bool,
               block: int = 256) -> jnp.ndarray:
    """int8 [m, n] -> int32 [m, n // (32//w)] lane words."""
    m, n = vals.shape
    per = 32 // w
    assert n % per == 0, (n, per)
    nw = n // per
    bm = min(8, m)
    bn = min(block, nw)
    grid = (pl.cdiv(m, bm), pl.cdiv(nw, bn))
    return pl.pallas_call(
        functools.partial(_pack_body, w),
        grid=grid,
        in_specs=[pl.BlockSpec((bm, bn * per), lambda i, j: (i, j))],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((m, nw), jnp.int32),
        interpret=interpret,
    )(vals)
