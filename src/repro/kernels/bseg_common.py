"""Shared BSEG pipeline machinery for the Pallas conv kernels.

Both the depthwise 1-D kernel (``bseg_conv1d``) and the cross-channel
2-D kernel (``bseg_conv2d``) run the same Fig. 6 schedule on every wide
multiply word: the ``n_i`` completed low lanes are emitted (guard bias
removed), the carried lanes are sliced into a resident low part that
stays on the datapath — re-biased, shifted down ``n_i`` lanes into the
next carry word (the DSP C-port / cascade) — and a high part that is
accumulated into the output buffer in fabric (Fig. 7).  This module is
that per-word step, factored out so the two kernels cannot drift.

Everything here runs *inside* a Pallas kernel body and is parameterized
over a ``WordSpec`` — the representation of the wide word on the chosen
datapath — instead of hard-coded int32:

  * ``int32``, 1 limb — the TPU INT32 lane (exact mod-2^32 wrap;
    shifts and masks are value-preserving below bit 32, so the word
    may wrap);
  * ``int32``, 2 limbs — the 33..64-bit DSP48E2/DSP58 words as hi/lo
    int32 limbs with explicit carry propagation (``core.limbs``):
    exactly how the 48-bit DSP ALU chains narrow adds through a carry.
    Compiles on any backend that has int32 — no ``jax_enable_x64``, no
    interpret-only gate.  The retained int64 single-word emulation in
    ``core.bseg`` / ``core.sdv`` is a *test oracle*, not an execution
    path;
  * ``float32``, 1 limb — the FP32M mantissa datapath.  fp32 *rounds*
    on overflow instead of wrapping, so the word must never leave the
    exact mantissa budget: the Eq. 9/10 guard-bit dimensioning keeps
    every lane inside [0, 2^L) and ``plan_bseg`` keeps the packed
    factor product inside ``w_word`` (<= 24), hence every intermediate
    is an exact integer below 2^24 and fp32 arithmetic is exact.
    Shifts become exact power-of-two divides + ``floor``; masks become
    ``mod``.

Kernel bodies use the limb-generic ``w_*`` word ops, which collapse to
plain array arithmetic on 1-limb specs.  Transport (kernel operands,
VMEM scratch) stores a 2-limb word as one int32 array with a leading
``(2,)`` plane axis (``planes[0]=lo``, ``planes[1]=hi``); see
``WordSpec.plane_shape`` / ``w_to_planes`` / ``w_from_planes``.

Lane values extracted from the word are tiny (within +-2^L), so the
fabric side — the adder tree and the output buffer — always accumulates
in ``FABRIC_DTYPE`` (int32, matching ``ref.conv2d_int_ref``) regardless
of the word representation.  Static Python loops over lanes only
(``n_lanes`` is tiny), no jnp dtype promotion surprises.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import List, Tuple

import jax.numpy as jnp

from repro.core import bseg as core_bseg
from repro.core import limbs as limb_ops
from repro.core.datapath import BSEGPlan
from repro.core.limbs import Limbs

#: dtype of the in-fabric adder tree / output accumulation buffer.  The
#: extracted lane values fit easily; int32 end-to-end matches the
#: integer conv oracle on every datapath.
FABRIC_DTYPE = jnp.int32


def bias_word_full(plan: BSEGPlan) -> int:
    """All ``n_lanes`` lanes loaded with the 2^(L-1) guard bias."""
    return sum((1 << (p * plan.lane)) * plan.bias
               for p in range(plan.n_lanes))


def bias_word_top(plan: BSEGPlan) -> int:
    """Fresh bias for the ``n_i`` lanes newly exposed at the top after
    the carry word shifts down ``n_i`` lanes."""
    return sum((1 << (p * plan.lane)) * plan.bias
               for p in range(plan.n_lanes - plan.n_i, plan.n_lanes))


@dataclasses.dataclass(frozen=True)
class WordSpec:
    """How a wide word is represented inside a kernel body.

    Attributes:
      dtype_name: jnp dtype name of the limb array ("int32" /
        "float32"; historical "int64" is accepted for the retained
        oracle spec but no execution path produces it).
      width: exact bits available in that representation (the datapath
        ``w_word``).
      exact_wrap: True when overflow wraps losslessly (integers); False
        when it rounds (fp32) and must be impossible by dimensioning.
      bias_full / bias_top: the guard-bias constants of
        ``bias_word_full`` / ``bias_word_top`` for the plan.
      limbs: 1 for words that fit a single array element (int32 lane /
        fp32 mantissa), 2 for the 33..64-bit DSP words held as hi/lo
        int32 limbs (``core.limbs``).

    The ``w_*`` methods are the limb-generic word algebra the kernel
    bodies are written against: on a 1-limb spec they collapse to
    plain jnp arithmetic, on a 2-limb spec they carry-propagate.  A
    "word" value is a jnp array (1 limb) or a ``core.limbs.Limbs``
    pair (2 limbs).
    """

    dtype_name: str
    width: int
    exact_wrap: bool
    bias_full: int
    bias_top: int
    limbs: int = 1

    @property
    def dtype(self):
        return jnp.dtype(self.dtype_name)

    @property
    def is_float(self) -> bool:
        return self.dtype_name == "float32"

    def const(self, value: int):
        """A scalar word-domain constant.  Integer representations wrap
        the value into the dtype's signed range (mod-2^bits, exactly
        the exact-wrap semantics of the datapath: a bias whose top bit
        lands on the sign bit is still value-preserving under the
        mask-based lane extraction); floats are exact by the guard-bit
        dimensioning."""
        if self.limbs == 2:
            return limb_ops.full((), value)
        if self.is_float:
            return jnp.float32(float(value))
        bits = 64 if self.dtype_name == "int64" else 32
        v = value % (1 << bits)
        if v >= 1 << (bits - 1):
            v -= 1 << bits
        return jnp.asarray(v, self.dtype)

    def scale(self, bits: int):
        """The lane scale 2^bits as a word-domain constant (multiply by
        it == shift left by ``bits``; exact in every representation)."""
        return self.const(1 << bits)

    def shift_down(self, word, bits: int):
        """word >> bits (floor semantics; exact power-of-two divide on
        the float representation) — ``core.bseg.shift_down``, shared so
        the jnp emulation and the kernels cannot drift."""
        if self.limbs == 2:
            return limb_ops.shift_right_logical(word, bits)
        return core_bseg.shift_down(word, bits)

    def mod_pow2(self, word, bits: int):
        """word mod 2^bits — mask on integers, exact float mod on the
        FP32M representation, limb-wise mask above bit 31."""
        if self.limbs == 2:
            return limb_ops.mod_pow2(word, bits)
        return core_bseg.mod_pow2(word, bits)

    def field(self, word, lsb: int, bits: int):
        """Extract the ``bits``-wide lane field starting at bit ``lsb``."""
        return self.mod_pow2(self.shift_down(word, lsb), bits)

    # -- limb-generic word algebra (kernel bodies use only these) -------

    def w_full(self, shape, value: int):
        """A word-domain array filled with ``value``."""
        if self.limbs == 2:
            return limb_ops.full(shape, value)
        return jnp.full(shape, self.const(value))

    def w_zeros(self, shape):
        return self.w_full(shape, 0)

    def w_full_like(self, word, value: int):
        shape = word.lo.shape if self.limbs == 2 else word.shape
        return self.w_full(shape, value)

    def w_add(self, a, b):
        return limb_ops.add(a, b) if self.limbs == 2 else a + b

    def w_sub(self, a, b):
        return limb_ops.sub(a, b) if self.limbs == 2 else a - b

    def w_mul(self, a, b):
        """Word * word, mod 2^64 on limbs; exact by dimensioning on the
        1-limb representations."""
        return limb_ops.mul(a, b) if self.limbs == 2 else a * b

    def w_or(self, a, b):
        """Bitwise OR (integer storage packing only)."""
        return limb_ops.bit_or(a, b) if self.limbs == 2 else a | b

    def w_shift_left(self, word, bits: int):
        if self.limbs == 2:
            return limb_ops.shift_left(word, bits)
        return word * self.scale(bits)

    def w_from_i32(self, x, *, signed: bool = True):
        """Lift an int32-domain array into the word domain
        (sign-extending when ``signed``)."""
        if self.limbs == 2:
            x = x.astype(FABRIC_DTYPE)
            return limb_ops.from_i32(x) if signed else limb_ops.from_u32(x)
        return x.astype(self.dtype)

    def w_lo_i32(self, word):
        """The int32 (``FABRIC_DTYPE``) value of a word whose
        mathematical value fits int32 — the hand-off from the word
        domain to the fabric adder tree.  Truncates mod 2^32 exactly
        like an int64 -> int32 astype, so the limb path and the int64
        oracle agree bit-for-bit."""
        if self.limbs == 2:
            return word.lo
        return word.astype(FABRIC_DTYPE)

    def w_map(self, word, fn):
        """Apply a shape-only op (index / broadcast / reshape /
        dynamic-slice) to each limb of the word."""
        if self.limbs == 2:
            return Limbs(fn(word.lo), fn(word.hi))
        return fn(word)

    # -- transport: words as plane-stacked int32 arrays -----------------

    def plane_shape(self, shape) -> tuple:
        """Array shape transporting words of logical ``shape``: a
        leading ``(2,)`` limb-plane axis on 2-limb specs."""
        return ((2,) + tuple(shape)) if self.limbs == 2 else tuple(shape)

    def w_to_planes(self, word):
        """Word -> transport array (identity on 1-limb specs)."""
        if self.limbs == 2:
            return limb_ops.stack_planes(word)
        return word

    def w_from_planes(self, arr):
        """Transport array -> word (identity on 1-limb specs)."""
        if self.limbs == 2:
            return limb_ops.from_planes(arr)
        return arr


@functools.lru_cache(maxsize=None)
def word_spec(plan: BSEGPlan) -> WordSpec:
    """The word representation for a plan's datapath.

    FP32M (``exact_wrap=False``) additionally requires that the word can
    never reach the first lossy bit: Eqs. 9/10 keep every lane inside
    [0, 2^L) and ``plan_bseg`` enforces ``wa_used + wb_used <= w_word``,
    which implies ``n_lanes * L + 2 <= w_word`` — so the whole word
    (and each ``kappa * iota`` product) stays an exact integer below
    2^w_word <= 2^24.  The assert documents that no-exact-wrap guard
    dimensioning; a plan violating it cannot come out of ``plan_bseg``.
    """
    spec = plan.spec
    # the biased accumulation word spans n_lanes * L bits (plan_bseg
    # enforces this fits w_word); on a no-exact-wrap word that is also
    # what makes fp32 arithmetic exact, on integers it keeps the top
    # lane's guard bias on the word.
    assert plan.n_lanes * plan.lane <= spec.w_word, (
        f"plan overruns the {spec.name} accumulator word: "
        f"{plan.n_lanes} lanes x L={plan.lane} vs w_word={spec.w_word}")
    # representation rule: fp32m keeps the exact float32 mantissa word;
    # integer words that fit 32 bits take one int32 limb; the wide
    # DSP48E2/DSP58 words take TWO int32 limbs with explicit carries.
    # core.bseg.word_dtype still says int64 for wide plans — that jnp
    # emulation is the differential ORACLE the limb path is pinned
    # against (tests force x64 for it), deliberately not the kernel
    # representation.
    if spec.exact_wrap and spec.w_word > 32:
        name, n_limbs = "int32", 2
    else:
        name = jnp.dtype(core_bseg.word_dtype(plan)).name
        n_limbs = 1
    return WordSpec(dtype_name=name,
                    width=spec.w_word,
                    exact_wrap=spec.exact_wrap,
                    bias_full=bias_word_full(plan),
                    bias_top=bias_word_top(plan),
                    limbs=n_limbs)


def word_dtype(plan: BSEGPlan):
    """Dtype of the limb arrays transporting packed factors / carry
    words for this plan (int32 for every integer datapath — wide words
    just use two limb planes of it; see ``WordSpec.plane_shape``)."""
    return word_spec(plan).dtype


def sdv_layout_bits(plan) -> int:
    """Bits one SDV storage word actually uses: the packed field plus
    the parked sign bits (signed-element layout only).  The single
    copy of the layout rule — the route gate (``ops``) and the storage
    spec below both consult it."""
    return plan.packed_width + (plan.n if plan.signed_a else 0)


@functools.lru_cache(maxsize=None)
def sdv_word_spec(plan) -> WordSpec:
    """The *storage*-word representation for an SDV plan's datapath:
    one int32 limb when both the datapath word and the storage layout
    (``sdv_layout_bits``) fit 32 bits, two int32 limb planes otherwise
    — the wide DSP48E2/DSP58 words, and also any hand-built plan whose
    layout overruns its own datapath word (the route layer sends those
    to ref; the limb planes keep the jnp ref decode lossless instead
    of failing at packing time).  SDV lanes carry no guard bias — the
    bias constants are zero.

    ``ops.prepare_sdv_weights`` and the GEMM/GEMV kernel bodies both
    consult this spec, so layout and compute cannot drift.  The
    storage encoding is always an integer bit-field pack — even for
    FP32M plans, whose *compute* never reaches the SDV kernels
    (``exact_wrap`` is False there: spill-over tracking relies on
    exact mod-2^w wrap, so ``select_packed_route`` refuses fp32m and
    the stored fields are only ever read back by the jnp ref decode).
    """
    spec = plan.spec
    wide = spec.w_word > 32 or sdv_layout_bits(plan) > 32
    return WordSpec(dtype_name="int32",
                    width=spec.w_word, exact_wrap=spec.exact_wrap,
                    bias_full=0, bias_top=0,
                    limbs=2 if wide else 1)


def pack_iota(samples, plan: BSEGPlan):
    """Pack ``n_i`` unsigned input samples (a sequence of ``n_i``
    equal-shape integer arrays, sample ``j`` landing in lane ``j``)
    into one input factor per position, in the plan's word
    representation."""
    ws = word_spec(plan)
    iota = ws.w_zeros(samples[0].shape)
    for j in range(plan.n_i):
        iota = ws.w_add(iota,
                        ws.w_shift_left(ws.w_from_i32(samples[j],
                                                      signed=False),
                                        j * plan.lane))
    return iota


def split_word(word, plan: BSEGPlan) -> Tuple[List[jnp.ndarray], "object"]:
    """One Fig. 6/7 post-multiply step on a wide word (any shape, in
    the plan's word representation — a jnp array or a ``Limbs`` pair).

    Returns ``(lanes, c_next)`` where ``lanes`` has ``plan.n_lanes``
    entries shaped like ``word`` in ``FABRIC_DTYPE``: the first ``n_i``
    are completed outputs (bias removed), the rest are the extracted
    high parts of the carried lanes; ``c_next`` is the re-biased carry
    word for the next step (resident low parts shifted down ``n_i``
    lanes, fresh bias on the newly exposed top lanes), staying in the
    word representation.
    """
    ws = word_spec(plan)
    n_i, n_lanes, L = plan.n_i, plan.n_lanes, plan.lane
    bias = ws.w_full_like(word, plan.bias)
    lanes = []
    for p in range(n_i):                       # completed outputs
        f = ws.field(word, p * L, L)
        lanes.append(ws.w_lo_i32(ws.w_sub(f, bias)))
    c_next = ws.w_full_like(word, ws.bias_top)
    for p in range(n_i, n_lanes):              # carried lanes: hi/lo slice
        f = ws.field(word, p * L, L)
        lo = ws.mod_pow2(f, plan.w_l)
        lanes.append(ws.w_lo_i32(ws.w_sub(ws.w_sub(f, lo), bias)))
        c_next = ws.w_add(c_next,
                          ws.w_shift_left(ws.w_add(lo, bias),
                                          (p - n_i) * L))
    return lanes, c_next
