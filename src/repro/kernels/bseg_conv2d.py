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
    tree — into the output position they belong to;
  * output channels ride the VPU lane dimension, the fused pipelines
    the sublanes: one word computation is a ``[kh*C_in, bh*bco]``
    elementwise multiply, i.e. every wide multiplier in the emulated
    array is busy every step.

Grid: (batch, H_out/bh, C_out/bco) — a block of ``bh`` output rows
per program (``ops.bseg_blocks`` sizes it from the shape).  The rows
of a block are independent BSEG pipelines; they sit side by side on
the lane axis (row ``b`` on lanes ``[b*bco, (b+1)*bco)``), so one loop
step advances every row's pipelines, and every tap group's, and a
step's fixed cost (the sample loads and transposes, the loop itself)
is paid once per block: UltraNet's first stage, 16 channels on 128
lanes, spent over 90% of each one-row step on it (PERF.md section 5).
On one TPU v5e the row blocks took the 416 frame's eight 3x3 stages
from 56.0 to 3.5 ms a frame, the first from 39.2 to 0.87 ms (PERF.md
section 6).
The wrapper materializes the fused input ``[W_pad, bh, kh*C_in]`` of
every block (the kh-1 row halo duplicated in HBM — BlockSpec offsets
are block-strided, so overlapping row blocks cannot be expressed in
the index map), so every in-kernel dynamic index is a leading index
of a ref; a step's samples become lane-repeated words through one
small 0/1 matmul each.  A position's adder-tree partial is an
``[8, bh*bco]`` tile carried through the loop until its last
contribution, then summed into its output row: no accumulator is read
back from VMEM.

Stride 1, 'same' padding (odd kw, or kh == kw == 1); the ops wrapper
owns padding, zero points and layout (see ``ops.packed_conv2d``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.datapath import BSEGPlan
from . import bseg_common


def _expand(xt: jnp.ndarray, bh: int, bco: int) -> jnp.ndarray:
    """``[khc, bh]`` samples -> ``[khc, bh*bco]``: row ``b``'s sample of
    each pipeline repeated over that row's ``bco`` lanes.  The repeat is
    a 0/1 matmul on the MXU, exact in bf16 because a staged sample is
    below 2^7 (``plan.w_i <= 7``) and each output sums one product.  A
    single row stays a ``[khc, 1]`` column, broadcast by the multiply."""
    if bh == 1:
        return xt
    n = bh * bco
    rows = jax.lax.broadcasted_iota(jnp.int32, (bh, n), 0)
    lanes = jax.lax.broadcasted_iota(jnp.int32, (bh, n), 1)
    rep = (lanes // bco == rows).astype(jnp.bfloat16)
    return jnp.dot(xt.astype(jnp.float32).astype(jnp.bfloat16), rep,
                   preferred_element_type=jnp.float32).astype(jnp.int32)


def _body(plan: BSEGPlan, n_groups: int, n_steps: int, bh: int, bco: int,
          x_ref, kap_ref, o_ref):
    """A block of ``bh`` output rows of one output-channel block.

    ``x_ref`` is the block's fused input ``[W_pad, bh, khc]`` (the
    kernel rows of every input channel side by side on the lane axis),
    so a step reads each of its samples as a ``[bh, khc]`` tile at a
    leading index, turns it into ``[khc, bh]`` and repeats it over the
    rows' output lanes: the wide word is a plain 2-D ``[khc, bh*bco]``
    array, row ``b`` on lanes ``[b*bco, (b+1)*bco)``.  One step runs
    every tap group's word, each with its own carry word.  The lanes are
    summed over ``(r, ci)`` with vreg adds into one ``[8, bh*bco]`` tile
    per output position; a position's tile is carried until its last
    contribution (the step whose completed lanes hold it), then summed
    over its 8 sublanes into its output row.
    """
    n_k, n_i, n_lanes = plan.n_k, plan.n_i, plan.n_lanes
    ws = bseg_common.word_spec(plan)
    khc, n = (kap_ref.shape[-2], kap_ref.shape[-1])
    kaps = [bseg_common.Limbs(kap_ref[0, g], kap_ref[1, g])
            if ws.limbs == 2 else kap_ref[g]              # [khc, n]
            for g in range(n_groups)]

    def step(t, carry):
        words, pending = carry
        tau = t * n_i
        # acc[p]: this step's partial tile of output position tau + p
        acc = list(pending) + [None] * (n_lanes - len(pending))
        words_next = []
        for g in range(n_groups):
            iota = bseg_common.pack_iota(
                [_expand(x_ref[0, 0, tau + g * n_k + j].astype(jnp.int32).T,
                         bh, bco) for j in range(n_i)], plan)   # [khc, n|1]
            word = ws.w_add(ws.w_mul(kaps[g], iota), words[g])  # [khc, n]
            # Fig. 7 slicing per pipeline, THEN the adder tree over (r, ci)
            lanes, c_next = bseg_common.split_word(word, plan)
            words_next.append(c_next)
            for p, lane in enumerate(lanes):
                part = jnp.sum(lane.reshape(khc // 8, 8, n), axis=0,
                               dtype=bseg_common.FABRIC_DTYPE)
                acc[p] = part if acc[p] is None else acc[p] + part
        for p in range(n_i):                   # positions completed here
            o_ref[0, 0, pl.ds(tau + p, 1), :] = jnp.sum(
                acc[p], axis=0, keepdims=True, dtype=bseg_common.FABRIC_DTYPE)
        return tuple(words_next), tuple(acc[n_i:])

    # the carry words are fori_loop carries: jnp arrays, or Limbs
    # pytrees on the 2-limb specs
    jax.lax.fori_loop(0, n_steps, step, (
        tuple(ws.w_full((khc, n), ws.bias_full) for _ in range(n_groups)),
        tuple(jnp.zeros((8, n), bseg_common.FABRIC_DTYPE)
              for _ in range(n_lanes - n_i))))


@functools.partial(jax.jit, static_argnames=("plan", "h_out", "w_out",
                                             "bh", "bco", "interpret"))
def bseg_conv2d(x_pad: jnp.ndarray, kappa: jnp.ndarray, *, plan: BSEGPlan,
                h_out: int, w_out: int, interpret: bool, bh: int = 1,
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
      bh: output rows per program (``ops.bseg_blocks``); H is padded
        up to a multiple of it and the extra rows dropped.
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
    n_cb = c_out // bco
    khc = kh * c_in
    khc8 = -(-khc // 8) * 8            # zero pipelines add exactly 0
    n_hb = -(-h_out // bh)
    h_rows = n_hb * bh
    n = bh * bco
    n_pos = n_steps * n_i
    x_pad = jnp.pad(x_pad, ((0, 0), (0, max(0, h_rows + kh - 1 - h_pad)),
                            (0, 0), (0, 0)))
    # fuse the (kernel row, input channel) pipelines into one lane axis:
    # xf[b, y, w, r*C_in + ci] = x_pad[b, y + r, w, ci] — the kh-row
    # halo is materialized here (kh x the small int8 frame) — then put a
    # block's rows beside each other at every column: xf[b, yb, w, i, :]
    # is row yb*bh + i
    xf = jnp.concatenate([x_pad[:, r:r + h_rows] for r in range(kh)],
                         axis=-1)                     # [B, h_rows, W_pad, khc]
    xf = jnp.pad(xf, ((0, 0), (0, 0), (0, 0), (0, khc8 - khc)))
    xf = xf.reshape(b, n_hb, bh, w_pad, khc8).transpose(0, 1, 3, 2, 4)
    # the factors repeated for every row of the block, in the word's
    # lane order: kap[..., c, cb*n + i*bco + co] = kappa[..., c, cb*bco + co]
    lead = kappa.shape[:-3]
    kap = kappa.reshape(lead + (khc, n_cb, 1, bco))
    kap = jnp.pad(kap, ((0, 0),) * len(lead)
                  + ((0, khc8 - khc), (0, 0), (0, 0), (0, 0)))
    kap = jnp.broadcast_to(kap, lead + (khc8, n_cb, bh, bco)).reshape(
        lead + (khc8, n_cb * n))
    grid = (b, n_hb, n_cb)
    kap_spec = pl.BlockSpec(lead + (khc8, n),
                            lambda ib, ih, ic: (0,) * len(lead) + (0, ic))
    y = pl.pallas_call(
        functools.partial(_body, plan, n_groups, n_steps, bh, bco),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, w_pad, bh, khc8),
                         lambda ib, ih, ic: (ib, ih, 0, 0, 0)),
            kap_spec,
        ],
        out_specs=pl.BlockSpec((1, 1, n_pos, n),
                               lambda ib, ih, ic: (ib, ih, 0, ic)),
        out_shape=jax.ShapeDtypeStruct((b, n_hb, n_pos, n_cb * n),
                                       jnp.int32),
        interpret=interpret,
    )(xf, kap)
    # output position = output column + n_k - 1
    y = y[:, :, n_k - 1:n_k - 1 + w_out]
    y = y.reshape(b, n_hb, w_out, n_cb, bh, bco).transpose(0, 1, 4, 2, 3, 5)
    return y.reshape(b, h_rows, w_out, c_out)[:, :h_out]


def bseg_conv2d_num_multiplies(h_out: int, w_out: int, c_in: int,
                               c_out: int, kh: int, kw: int,
                               plan: BSEGPlan) -> int:
    """Wide multiplies one ``bseg_conv2d`` launch spends — the
    operational-density currency.  Every (output row, kernel row, input
    channel, output channel, tap group, step) is one wide multiply."""
    n_groups = -(-kw // plan.n_k)
    n_steps = -(-(w_out + plan.n_k - 1) // plan.n_i)
    return h_out * kh * c_in * c_out * n_groups * n_steps
