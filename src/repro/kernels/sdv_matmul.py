"""SDV packed GEMM Pallas kernel (paper Sec. III-C, batched form).

Generalizes ``kernels/sdv_matvec`` from a GEMV to a blocked, batched
GEMM: the activation operand is a full ``[R, K]`` row block (R =
flattened batch x tokens), so the one wide int32 multiply per (row,
group, k) is amortized over ``n`` lane-packed output channels *and*
reused across the row block — the dominant serving/training GEMM
shapes, not just single-vector decode.

Same on-chip architecture as the GEMV kernel:

  * HBM storage: one int32 word per (output-group, k).  Signed
    elements store the sign-sliced remainder fields (the D word) with
    the n sign bits parked above the packed field; unsigned elements
    store the lane fields directly (no sign bits — the protection bit
    is a leading zero, Sec. III-C);
  * the pre-adder ``packed = D - A`` is materialized in-kernel for the
    signed layout (Fig. 3); the unsigned layout skips it;
  * the fractured-LUT reference multiplier: 2-LSB products mod 4;
  * the spill-over tracker: mod-4 mismatch -> spill in [-1, 1] for
    signed operands, [0, 2] when both operands are unsigned (Fig. 4);
  * the Eq. 3 extractor on the final k step.

Grid: (R/br, G/bg, K/bk) with K innermost; the accumulator word and
the spill totals live in VMEM scratch across K steps.  Rows are
blocked at GEMM granularity (default 128) instead of the GEMV
kernel's 8.  Both wrappers hand the kernel a K-major ``[bk, br]``
activation block, so each K step reads one sublane row of each
operand ref.

The body is *word-generic* (``bseg_common.sdv_word_spec``): one int32
limb for plans whose storage layout fits the 32-bit TPU lane, two
carry-propagating int32 limbs (``core.limbs``) for the wide
DSP48E2/DSP58 words — the same hi/lo + carry trick the 48-bit DSP ALU
plays, so every plan compiles on any backend with int32 (no
``jax_enable_x64``, no interpret-only gate).  Every mask/shift below
the datapath word width is value-preserving in either representation —
mod-2^64 limb wrap and hardware wrap at 2^48 agree on all bits the
Eq. 3 extractor ever reads — so one body serves all exact-wrap
datapaths.  The spill totals and the lane outputs are tiny and stay
int32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core import limbs as limb_ops
from repro.core.datapath import SDVPlan
from repro.core.limbs import Limbs
from . import bseg_common


def _lsb2(d_word, sign_bits, i: int, lane: int, w_a: int, signed_a: bool):
    """Two LSBs of element i (a_i & 3) from the stored fields."""
    if isinstance(d_word, Limbs):
        r2 = limb_ops.field(d_word, i * lane, 2).lo
    else:
        r2 = (d_word >> (i * lane)) & 3
    if not signed_a or w_a >= 3:
        return r2                       # sign weight 2^(w_a-1) = 0 (mod 4)
    s = (sign_bits >> i) & 1
    return (r2 + 2 * s) & 3             # signed w_a == 2: a = r - 2 s


def _body(plan_n: int, lane: int, w_a: int, signed_a: bool, signed: bool,
          sign_shift: int, nsteps_k: int, bk: int,
          ws: bseg_common.WordSpec,
          x_ref, w_ref, o_ref, word_ref, spill_ref):
    """Shared GEMM/GEMV kernel body.

    The activation block is K-major ``[bk, rows]`` and the storage
    block ``[bk, bg]`` (leading ``(2,)`` limb-plane axis on 2-limb
    specs), so step ``j`` of the K loop reads row ``j`` of each ref —
    a dynamic *sublane* offset, which Mosaic lowers — and never slices
    a loaded value.  ``ws`` is the storage-word representation
    (``bseg_common.sdv_word_spec``): one int32 limb, or two int32 limb
    planes for the wide DSP48E2/DSP58 words.  The accumulator word is a
    ``[rows, bg]`` scratch (limb planes on 2-limb specs) and the spill
    totals an ``[n, rows, bg]`` scratch; the output block is
    ``[n, rows, bg]`` — lane index leading, so no array ever carries
    the tiny lane count on the 128-wide vector lane axis.
    """
    k_step = pl.program_id(2)
    n = plan_n
    two_limb = ws.limbs == 2

    @pl.when(k_step == 0)
    def _init():
        word_ref[...] = jnp.zeros_like(word_ref)
        spill_ref[...] = jnp.zeros_like(spill_ref)

    def read_stored(j):
        if two_limb:
            return Limbs(w_ref[0, pl.ds(j, 1), :], w_ref[1, pl.ds(j, 1), :])
        return w_ref[pl.ds(j, 1), :]                                 # [1, bg]

    def step(j, carry):
        word, spills = carry
        xk = jnp.transpose(x_ref[pl.ds(j, 1), :].astype(jnp.int32))  # [rows,1]
        stored = read_stored(j)
        d_word = ws.mod_pow2(stored, sign_shift)
        if signed_a:
            if two_limb:
                sign_bits = limb_ops.field(stored, sign_shift, n).lo
            else:
                sign_bits = (stored >> sign_shift) & ((1 << n) - 1)
            # ---- the pre-adder: packed = D - A (Fig. 3) ----------------
            a_word = ws.w_full_like(d_word, 0)
            for i in range(n):
                bit = (sign_bits >> i) & 1
                a_word = ws.w_add(
                    a_word,
                    ws.w_shift_left(ws.w_from_i32(bit, signed=False),
                                    i * lane + w_a - 1))
            packed = ws.w_sub(d_word, a_word)                         # [1,bg]
        else:
            sign_bits = jnp.zeros_like(ws.w_lo_i32(d_word))
            packed = d_word               # unsigned: plain concatenation
        # ---- wide MAC --------------------------------------------------
        word2 = ws.w_add(word, ws.w_mul(packed, ws.w_from_i32(xk)))  # [br,bg]
        # ---- mod-4 spill tracking (fractured-LUT reference) ------------
        x4 = xk & 3                                                   # [br,1]
        new_spills = []
        for i in range(1, n + 1):
            prev = ws.w_lo_i32(ws.field(word, i * lane, 2))
            obs = ws.w_lo_i32(ws.field(word2, i * lane, 2))
            if i < n:
                p4 = (_lsb2(d_word, sign_bits, i, lane, w_a,
                            signed_a) * x4) & 3
            else:
                p4 = 0                    # virtual observer lane
            mm = (obs - prev - p4) & 3
            # signed products spill [-1, 1]; unsigned spill [0, 2]
            delta = jnp.where(mm == 3, -1, mm) if signed else mm
            new_spills.append(spills[i - 1] + delta.astype(jnp.int32))
        return word2, tuple(new_spills)

    word, spills = jax.lax.fori_loop(
        0, bk, step, (ws.w_from_planes(word_ref[...]),
                      tuple(spill_ref[i] for i in range(n))))
    word_ref[...] = ws.w_to_planes(word)
    for i in range(n):
        spill_ref[i] = spills[i]

    @pl.when(k_step == nsteps_k - 1)
    def _extract():
        # Eq. 3:  R̂_i = (2^L S_i + R_i) - S_{i-1}
        for i in range(n):
            field = ws.field(word, i * lane, lane)
            s_i = spills[i]
            # lane results are exact dot products that fit int32 on
            # every plan; the wide-word path computes them mod 2^64 in
            # the limb domain and hands back the low limb — the same
            # truncation as the int64 oracle's astype(int32)
            if two_limb:
                acc = limb_ops.add(limb_ops.shift_left(
                    limb_ops.from_i32(s_i), lane), field)
                if i > 0:
                    acc = limb_ops.sub(acc, limb_ops.from_i32(spills[i - 1]))
                o_ref[i] = acc.lo
            else:
                s_prev = spills[i - 1] if i > 0 else 0
                o_ref[i] = ((s_i.astype(ws.dtype) << lane)
                            + field - s_prev).astype(jnp.int32)


def sdv_call(x_t: jnp.ndarray, w_words: jnp.ndarray, *, plan: SDVPlan,
             br: int, bg: int, bk: int, interpret: bool) -> jnp.ndarray:
    """The SDV pallas_call shared by the GEMM and GEMV wrappers.

    ``x_t`` is the K-major ``[K, R]`` activation; returns ``[R, G, n]``
    int32 exact per-lane dot products.  Grid ``(R/br, G/bg, K/bk)``
    with K innermost; ragged R/G edge blocks only feed padding lanes
    and rows, which the caller trims.
    """
    k, r = x_t.shape
    g = w_words.shape[-1]
    n, lane = plan.n, plan.lane
    ws = bseg_common.sdv_word_spec(plan)
    assert ws.exact_wrap, plan.spec.name     # spill tracking needs wrap
    assert bseg_common.sdv_layout_bits(plan) <= plan.spec.w_word, plan
    assert w_words.dtype == ws.dtype, (w_words.dtype, ws.dtype)
    assert w_words.ndim == (3 if ws.limbs == 2 else 2), \
        (w_words.shape, ws.limbs)
    br = min(br, r)
    bg = min(bg, g)
    bk = min(bk, k)
    assert k % bk == 0, (k, bk)
    signed = plan.signed_a or plan.signed_b
    grid = (pl.cdiv(r, br), pl.cdiv(g, bg), k // bk)
    if ws.limbs == 2:
        w_spec = pl.BlockSpec((2, bk, bg), lambda ir, ig, ik: (0, ik, ig))
    else:
        w_spec = pl.BlockSpec((bk, bg), lambda ir, ig, ik: (ik, ig))
    lanes = pl.pallas_call(
        functools.partial(_body, n, lane, plan.w_a, plan.signed_a, signed,
                          plan.packed_width, k // bk, bk, ws),
        grid=grid,
        in_specs=[
            pl.BlockSpec((bk, br), lambda ir, ig, ik: (ik, ir)),
            w_spec,
        ],
        out_specs=pl.BlockSpec((n, br, bg), lambda ir, ig, ik: (0, ir, ig)),
        out_shape=jax.ShapeDtypeStruct((n, r, g), jnp.int32),
        scratch_shapes=[
            pltpu.VMEM(ws.plane_shape((br, bg)), ws.dtype),
            pltpu.VMEM((n, br, bg), jnp.int32),
        ],
        interpret=interpret,
    )(x_t, w_words)
    return jnp.transpose(lanes, (1, 2, 0))                        # [R, G, n]


@functools.partial(jax.jit, static_argnames=("plan", "br", "bg", "bk",
                                             "interpret"))
def sdv_matmul(x_q: jnp.ndarray, w_words: jnp.ndarray, *, plan: SDVPlan,
               interpret: bool, br: int = 128, bg: int = 128,
               bk: int = 512) -> jnp.ndarray:
    """Packed GEMM.

    Args:
      x_q: [R, K] integer activations (row-major), values within w_b
        bits (signed or unsigned per ``plan.signed_b``).
      w_words: [K, G] storage words (``prepare_sdv_weights``) in the
        plan's transport layout — int32, with a leading (2,) limb-plane
        axis ([2, K, G]) for wide (DSP48E2/DSP58) words.
      plan: SDV lane plan on any exact-wrap datapath.
      interpret: run the Pallas interpreter (CPU) instead of Mosaic.

    Returns:
      [R, G, n] int32 — exact per-lane dot products (dequantize
      outside).  K must be a multiple of ``bk`` (zero-pad K outside:
      zero activations produce zero products and zero spills, so the
      padding is exact).  The activations are handed to the kernel
      K-major (one XLA transpose of the small activation operand), so
      the GEMM and the GEMV share one body and one layout.
    """
    return sdv_call(jnp.transpose(x_q), w_words, plan=plan, br=br, bg=bg,
                    bk=bk, interpret=interpret)


def sdv_num_multiplies(rows: int, m: int, k: int, plan: SDVPlan) -> int:
    """Wide int32 multiplies an SDV GEMM spends on an ``[rows, k] @
    [k, m]`` product — the paper's operational-density currency
    (``bseg_num_multiplies`` analogue for SDV): one multiply covers
    ``plan.n`` output channels, so the reduction vs the naive count
    ``rows * m * k`` is exactly the packing density."""
    groups = -(-m // plan.n)
    return rows * groups * k
