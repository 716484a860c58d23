"""Cross-channel BSEG packed conv2d Pallas kernel (paper Sec. III-D).

Generalizes ``kernels/bseg_conv1d`` from a depthwise 1-D conv to the
full dense conv2d the paper's UltraNet evaluation is built on: a
``kh x kw`` conv over ``C_in`` input channels becomes ONE kernel launch
instead of ``kh`` broadcast-materialized jnp passes (the seed
``models/ultranet._conv2d_bseg_jnp`` path).

Mapping (Figs. 6/7):

  * every kernel row r of every input channel ci is a 1-D BSEG row
    conv: kw taps packed (reversed, pre-adder) into ceil(kw/n_k) tap
    groups, n_i input samples packed per step — one wide multiply (in
    the plan's word representation: one int32 limb for the INT32 lane,
    float32 for FP32M, two carry-propagating int32 limbs for the wide
    DSP48E2/DSP58 words — see ``bseg_common.WordSpec``) performs
    n_k * n_i MACs;
  * the (r, ci) pipelines are *fused into one vectorized axis* of size
    kh * C_in: their wide words advance in lock-step through the Fig. 6
    schedule, each with its own packed-partial carry word (the DSP
    C-port / cascade), kept per tap group as a fori_loop carry;
  * guard-bit slicing (Fig. 7) happens per lane per pipeline *before*
    the cross-channel reduction: the resident low part is re-biased
    back onto the datapath, only the extracted high parts and the
    completed low lanes are summed over (r, ci) — the paper's adder
    tree — into the VMEM row accumulator;
  * output channels ride the VPU lane dimension (``bco`` lanes), the
    fused pipelines the sublanes: one word computation is a
    ``[kh*C_in, bco]`` elementwise multiply, i.e. every wide multiplier
    in the emulated array is busy every step.

Grid: (batch, H_out, C_out/bco) — one output row per program.  The
wrapper materializes each output row's fused input ``[W_pad,
kh*C_in]`` (the kh-1 row halo duplicated in HBM — BlockSpec offsets
are block-strided, so overlapping row blocks cannot be expressed in
the index map), so every in-kernel dynamic index is a sublane row of a
ref; the accumulator buffer ``[n_steps*n_i + n_lanes, bco]`` lives in
VMEM scratch.

Stride 1, 'same' padding (odd kw, or kh == kw == 1); the ops wrapper
owns padding, zero points and layout (see ``ops.packed_conv2d``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.datapath import BSEGPlan
from . import bseg_common


def _body(plan: BSEGPlan, n_groups: int, n_steps: int, w_out: int,
          x_ref, kap_ref, o_ref, buf_ref):
    """One output row of one output-channel block.

    ``x_ref`` is the row's fused input ``[W_pad, kh*C_in]`` (the kernel
    rows of every input channel side by side on the lane axis), so a
    step reads its ``n_i`` samples as sublane rows of the ref; the
    packed factor is turned into a ``[kh*C_in, 1]`` column once per
    step so the wide word is a plain 2-D ``[kh*C_in, bco]`` array.
    """
    n_k, n_i = plan.n_k, plan.n_i
    ws = bseg_common.word_spec(plan)
    khc, bco = (kap_ref.shape[-2], kap_ref.shape[-1])

    buf_ref[...] = jnp.zeros_like(buf_ref)

    for g in range(n_groups):
        if ws.limbs == 2:
            kap_g = bseg_common.Limbs(kap_ref[0, g], kap_ref[1, g])
        else:
            kap_g = kap_ref[g]                             # [khc, bco]

        def step(t, carry, g=g, kap_g=kap_g):
            tau = t * n_i
            iota = bseg_common.pack_iota(
                [x_ref[0, 0, pl.ds(tau + g * n_k + j, 1), :]
                 for j in range(n_i)], plan)               # [1, khc]
            iota = ws.w_map(iota, jnp.transpose)           # [khc, 1]
            word = ws.w_add(ws.w_mul(kap_g, iota), carry)  # [khc, bco]
            # Fig. 7 slicing per pipeline, THEN the adder tree over (r, ci)
            lanes, c_next = bseg_common.split_word(word, plan)
            for p, lane in enumerate(lanes):
                buf_ref[pl.ds(tau + p, 1), :] += jnp.sum(
                    lane, axis=0, keepdims=True, dtype=jnp.int32)
            return c_next

        # the carry word is a fori_loop carry: a jnp array, or a Limbs
        # pytree on the 2-limb specs
        jax.lax.fori_loop(0, n_steps, step, ws.w_full((khc, bco),
                                                      ws.bias_full))

    # buffer index = output column + n_k - 1
    o_ref[0, 0] = buf_ref[pl.ds(n_k - 1, w_out), :]


@functools.partial(jax.jit, static_argnames=("plan", "h_out", "w_out",
                                             "bco", "interpret"))
def bseg_conv2d(x_pad: jnp.ndarray, kappa: jnp.ndarray, *, plan: BSEGPlan,
                h_out: int, w_out: int, interpret: bool,
                bco: int = 128) -> jnp.ndarray:
    """Dense stride-1 conv2d through the BSEG datapath.

    Args:
      x_pad: [B, H_pad, W_pad, C_in] int8, unsigned values in
        [0, 2^w_i), already 'same'-padded on H (H_pad = h_out + kh - 1)
        and padded on W to cover the step schedule (see
        ``ops.packed_conv2d`` for the exact amount).
      kappa: [G, kh, C_in, C_out] packed kernel-row factors in the
        plan's transport layout (``bseg_common.word_dtype``; one per
        tap group, pre-adder applied at weight-prep time).  Wide
        (2-limb) plans carry a leading (2,) limb-plane axis:
        [2, G, kh, C_in, C_out] int32.
      plan: BSEG plan on any supported datapath (1-limb int32 / fp32,
        or 2-limb int32 for the wide DSP words — see
        ``bseg_common.WordSpec``).
      h_out / w_out: output frame size.
      interpret: run the Pallas interpreter (CPU) instead of Mosaic.
      bco: output-channel block size (must divide C_out; the ops
        wrapper downgrades it if not).

    Returns:
      [B, h_out, w_out, C_out] int32 — exact correlation totals summed
      over kernel rows and input channels (guard bias removed; any
      zero-point correction happens in the ops wrapper).
    """
    ws = bseg_common.word_spec(plan)
    b, h_pad, w_pad, c_in = x_pad.shape
    if ws.limbs == 2:
        two, n_groups, kh, kc, c_out = kappa.shape
        assert two == 2, kappa.shape
    else:
        n_groups, kh, kc, c_out = kappa.shape
    assert kc == c_in, (kc, c_in)
    assert h_pad >= h_out + kh - 1, (h_pad, h_out, kh)
    n_k, n_i = plan.n_k, plan.n_i
    n_steps = -(-(w_out + n_k - 1) // n_i)
    need = (n_steps - 1) * n_i + (n_groups - 1) * n_k + n_i
    assert w_pad >= need, (w_pad, need)
    bco = min(bco, c_out)
    assert c_out % bco == 0, (c_out, bco)
    khc = kh * c_in
    buf_len = n_steps * n_i + plan.n_lanes + 8
    # fuse the (kernel row, input channel) pipelines into one lane axis:
    # xf[b, y, w, r*C_in + ci] = x_pad[b, y + r, w, ci] — the kh-row
    # halo is materialized here (kh x the small int8 frame, staged as
    # int32 rows), so each grid step's block is one plain output row
    xf = jnp.concatenate([x_pad[:, r:r + h_out] for r in range(kh)],
                         axis=-1).astype(jnp.int32)   # [B, h_out, W_pad, khc]
    kap = kappa.reshape(kappa.shape[:-3] + (khc, c_out))
    grid = (b, h_out, c_out // bco)
    if ws.limbs == 2:
        kap_spec = pl.BlockSpec((2, n_groups, khc, bco),
                                lambda ib, ih, ic: (0, 0, 0, ic))
    else:
        kap_spec = pl.BlockSpec((n_groups, khc, bco),
                                lambda ib, ih, ic: (0, 0, ic))
    return pl.pallas_call(
        functools.partial(_body, plan, n_groups, n_steps, w_out),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, w_pad, khc),
                         lambda ib, ih, ic: (ib, ih, 0, 0)),
            kap_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, w_out, bco),
                               lambda ib, ih, ic: (ib, ih, 0, ic)),
        out_shape=jax.ShapeDtypeStruct((b, h_out, w_out, c_out), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM((buf_len, bco), jnp.int32),
        ],
        interpret=interpret,
    )(xf, kap)


def bseg_conv2d_num_multiplies(h_out: int, w_out: int, c_in: int,
                               c_out: int, kh: int, kw: int,
                               plan: BSEGPlan) -> int:
    """Wide multiplies one ``bseg_conv2d`` launch spends — the
    operational-density currency.  Every (output row, kernel row, input
    channel, output channel, tap group, step) is one wide multiply."""
    n_groups = -(-kw // plan.n_k)
    n_steps = -(-(w_out + plan.n_k - 1) // plan.n_i)
    return h_out * kh * c_in * c_out * n_groups * n_steps
