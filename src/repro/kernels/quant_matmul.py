"""Unpack-in-kernel quantized matmul (the ``packed_memory`` path).

Weights live in HBM as int32 lane words (32/w quantized values each, the
paper's packing applied to the *memory* side of the TPU roofline) and
are expanded to the compute dtype inside VMEM, right before the MXU dot.
HBM traffic for the weight operand drops by 16/w vs bf16 — on the
memory-bound decode shapes this moves the dominant roofline term by the
same factor.

Blocking: grid (m/bm, n/bn, k/bk), k innermost; fp32 accumulation in a
VMEM scratch tile; per-output-channel scales fused on the final k step.
Block shapes default to MXU-aligned multiples of 128.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _body(w: int, nsteps_k: int, x_ref, wp_ref, scale_ref, o_ref, acc_ref):
    k_step = pl.program_id(2)

    @pl.when(k_step == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    per = 32 // w
    words = wp_ref[...]                                    # [bk, bn/per] i32
    x = x_ref[...].astype(jnp.float32)                     # [bm, bk]
    # field i of word j is output column j*per + i: one MXU dot per
    # field, each into its own accumulator plane (the column interleave
    # happens outside the kernel, on the small output)
    for i in range(per):
        f = (words >> (i * w)) & ((1 << w) - 1)
        f = jnp.where(f >= (1 << (w - 1)), f - (1 << w), f)
        acc_ref[i] += jax.lax.dot_general(
            x, f.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(k_step == nsteps_k - 1)
    def _flush():
        for i in range(per):
            o_ref[i] = acc_ref[i] * scale_ref[pl.ds(i, 1), :]


@functools.partial(jax.jit, static_argnames=("w", "bm", "bn", "bk",
                                             "interpret"))
def quant_matmul(x: jnp.ndarray, w_packed: jnp.ndarray, scale: jnp.ndarray,
                 *, w: int, interpret: bool, bm: int = 128, bn: int = 1024,
                 bk: int = 512) -> jnp.ndarray:
    """x [m, k] (bf16/f32)  @  packed weights [k, n/(32/w)] int32 -> [m, n].

    ``scale`` is the per-output-channel dequantization scale [n].  The
    weight block is ``[bk, bn/(32/w)]`` words; the default ``bn`` keeps
    that lane width a multiple of 128 at w=4.
    """
    m, k = x.shape
    per = 32 // w
    nw = w_packed.shape[1]
    n = nw * per
    bm = min(bm, m)
    bn = min(bn, n)
    bk = min(bk, k)
    assert n % bn == 0 and k % bk == 0 and bn % per == 0, (m, n, k, bm, bn, bk)
    bw = bn // per
    grid = (pl.cdiv(m, bm), n // bn, k // bk)
    planes = pl.pallas_call(
        functools.partial(_body, w, k // bk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bw), lambda i, j, kk: (kk, j)),
            pl.BlockSpec((per, bw), lambda i, j, kk: (0, j)),
        ],
        out_specs=pl.BlockSpec((per, bm, bw), lambda i, j, kk: (0, i, j)),
        out_shape=jax.ShapeDtypeStruct((per, m, nw), jnp.float32),
        scratch_shapes=[pltpu.VMEM((per, bm, bw), jnp.float32)],
        interpret=interpret,
    )(x, w_packed, scale.reshape(nw, per).T)
    return jnp.transpose(planes, (1, 2, 0)).reshape(m, n)
