"""SDV packed GEMV Pallas kernel (paper Sec. III-C on the TPU VPU).

One int32 multiply carries ``n`` low-bit MACs: n output channels are
lane-packed into a single multiplicand word, the activation is the
shared multiplier.  The kernel reproduces the paper's architecture
end to end, on-chip:

  * HBM storage: one int32 word per (output-group, k) holding the
    sign-sliced remainder fields (the D word) plus the collected sign
    bits parked above the packed field;
  * the pre-adder: ``packed = D - A`` is materialized inside the kernel
    (Fig. 3) — two VPU ops, no extra memory traffic;
  * the fractured-LUT reference multiplier: 2-LSB products mod 4;
  * the spill-over tracker: mod-4 mismatch -> spill in [-1, 1],
    accumulated per lane (Fig. 4);
  * the Eq. 3 extractor on the final k step.

Grid: (B/bb, G/bg, K/bk) with K innermost; the accumulator word and the
spill totals live in VMEM scratch across K steps.  Layouts are K-major
so the per-step slice is a sublane read.

The kernel (pre-adder, spill tracker, extractor) is shared with the
batched GEMM — ``kernels/sdv_matmul.sdv_call``, which takes the
K-major activation layout this wrapper is given; this wrapper is the
decode-micro-batch special case (8-row blocks).  The body is
word-generic (``bseg_common.sdv_word_spec``): one int32 limb, or two
carry-propagating int32 limb planes for the wide DSP48E2/DSP58 words
— every plan compiles on any backend with int32.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core.datapath import SDVPlan
from .sdv_matmul import sdv_call


@functools.partial(jax.jit, static_argnames=("plan", "bb", "bg", "bk",
                                             "interpret"))
def sdv_matvec(x_t: jnp.ndarray, w_words: jnp.ndarray, *, plan: SDVPlan,
               interpret: bool, bb: int = 8, bg: int = 128,
               bk: int = 512) -> jnp.ndarray:
    """Packed GEMV.

    Args:
      x_t: [K, B] int8 activations (K-major), values within w_b bits.
      w_words: [K, G] storage words (from ``prepare_sdv_weights``) in
        the plan's transport layout (leading (2,) limb-plane axis for
        wide words: [2, K, G]).
      plan: SDV lane plan on any exact-wrap datapath.
      interpret: run the Pallas interpreter (CPU) instead of Mosaic.

    Returns:
      [B, G, n] int32 — exact per-lane dot products (dequantize outside).
    """
    return sdv_call(x_t, w_words, plan=plan, br=bb, bg=bg, bk=bk,
                    interpret=interpret)
