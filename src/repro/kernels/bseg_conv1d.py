"""BSEG packed depthwise causal conv1d Pallas kernel (paper Sec. III-D).

Channels ride the VPU lane dimension; the Fig. 6 pipeline advances
``n_i`` input samples per wide multiply, with the packed-partial carry
word (the DSP C-port / cascade) held in VMEM scratch per kernel group.
Guard-bit biasing keeps every lane inside [0, 2^L); between steps each
carried lane is sliced into a resident low part (stays on the datapath)
and a high part that is accumulated straight into the output buffer
(Fig. 7's "tracked in fabric").

One multiply performs n_k * n_i useful MACs; for the mamba2 / RG-LRU
short-conv shapes (n = 4 taps, W4A4: n_k = n_i = 2) this is 4 MACs per
int32 multiply — a 4x multiplier-count reduction over the naive map.

Inputs must be *unsigned* within w_i (zero-point shifted by the ops
wrapper, per the paper's signed-kernel/unsigned-input dimensioning).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.datapath import BSEGPlan
from . import bseg_common


def _body(plan: BSEGPlan, n_groups: int, n_steps: int, s_out: int,
          x_ref, kap_ref, o_ref, buf_ref, carry_ref):
    """Every dynamic index is a sublane offset into a ref: the step's
    input samples, the carry word and the output buffer rows are read
    and written in place (``[1, bc]`` rows), never sliced out of a
    loaded value."""
    n_k, n_i = plan.n_k, plan.n_i
    ws = bseg_common.word_spec(plan)
    two_limb = ws.limbs == 2

    buf_ref[...] = jnp.zeros_like(buf_ref)
    # carry scratch holds one word per (group, channel); on a 2-limb
    # spec the scratch has a leading (2,) limb-plane axis
    init_shape = carry_ref.shape[1:] if two_limb else carry_ref.shape
    carry_ref[...] = ws.w_to_planes(ws.w_full(init_shape, ws.bias_full))

    def row(ref, g):
        """Word row ``g`` ([1, bc]) of a word-domain ref."""
        if two_limb:
            return bseg_common.Limbs(ref[0, pl.ds(g, 1), :],
                                     ref[1, pl.ds(g, 1), :])
        return ref[pl.ds(g, 1), :]

    def write_carry(g, word):
        if two_limb:
            carry_ref[0, pl.ds(g, 1), :] = word.lo
            carry_ref[1, pl.ds(g, 1), :] = word.hi
        else:
            carry_ref[pl.ds(g, 1), :] = word

    def step(t, _):
        tau = t * n_i
        upd = None
        for g in range(n_groups):
            iota = bseg_common.pack_iota(
                [x_ref[0, pl.ds(tau + g * n_k + j, 1), :]
                 for j in range(n_i)], plan)                 # [1, bc]
            # wide MAC + C port
            word = ws.w_add(ws.w_mul(row(kap_ref, g), iota),
                            row(carry_ref, g))
            # emit completed lanes + slice carried lanes (Fig. 7)
            lanes, c_next = bseg_common.split_word(word, plan)
            write_carry(g, c_next)
            upd = lanes if upd is None else [u + l for u, l in
                                             zip(upd, lanes)]
        for p, lane in enumerate(upd):
            buf_ref[pl.ds(tau + p, 1), :] += lane
        return 0

    jax.lax.fori_loop(0, n_steps, step, 0)
    o_ref[0] = buf_ref[pl.ds(n_k - 1, s_out), :]


@functools.partial(jax.jit, static_argnames=("plan", "s_out", "bc",
                                             "interpret"))
def bseg_conv1d(x_pad: jnp.ndarray, kappa: jnp.ndarray, *, plan: BSEGPlan,
                s_out: int, interpret: bool,
                bc: int = 128) -> jnp.ndarray:
    """Depthwise causal conv through the BSEG datapath.

    Args:
      x_pad: [B, S_pad, C] int8, unsigned values in [0, 2^w_i), already
        left-padded with n-1 zeros (plus any alignment padding at the
        right end — see ops.prepare for the exact amount).
      kappa: [G, C] packed kernel factors in the plan's transport
        layout (``bseg_common.word_dtype``; one per tap group,
        pre-adder applied at weight-prep time).  Wide (2-limb) plans
        carry a leading (2,) limb-plane axis: [2, G, C] int32.
      plan: BSEG plan on any supported datapath (1-limb int32 / fp32,
        or 2-limb int32 for the wide DSP words — see
        ``bseg_common.WordSpec``).
      s_out: number of output samples.
      interpret: run the Pallas interpreter (CPU) instead of Mosaic.

    Returns:
      [B, S_out, C] int32 — exact correlation totals (bias removed).
    """
    ws = bseg_common.word_spec(plan)
    b, s_pad, c = x_pad.shape
    n_groups = kappa.shape[1] if ws.limbs == 2 else kappa.shape[0]
    n_i, n_k = plan.n_i, plan.n_k
    n_steps = -(-(s_out + n_k - 1) // n_i)
    need = (n_steps - 1) * n_i + (n_groups - 1) * n_k + n_i
    assert s_pad >= need, (s_pad, need)
    bc = min(bc, c)
    assert c % bc == 0
    # staged as int32 so every per-step sample read is a 32-bit
    # sublane row (the int8 layout packs four rows per sublane)
    x_pad = x_pad.astype(jnp.int32)
    buf_len = n_steps * n_i + plan.n_lanes + 8
    grid = (b, c // bc)
    if ws.limbs == 2:
        kap_spec = pl.BlockSpec((2, n_groups, bc),
                                lambda ib, ic: (0, 0, ic))
    else:
        kap_spec = pl.BlockSpec((n_groups, bc), lambda ib, ic: (0, ic))
    return pl.pallas_call(
        functools.partial(_body, plan, n_groups, n_steps, s_out),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, s_pad, bc), lambda ib, ic: (ib, 0, ic)),
            kap_spec,
        ],
        out_specs=pl.BlockSpec((1, s_out, bc), lambda ib, ic: (ib, 0, ic)),
        out_shape=jax.ShapeDtypeStruct((b, s_out, c), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((buf_len, bc), jnp.int32),
            pltpu.VMEM(ws.plane_shape((n_groups, bc)), ws.dtype),
        ],
        interpret=interpret,
    )(x_pad, kappa)
