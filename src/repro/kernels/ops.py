"""Public jit'd wrappers around the Pallas kernels.

Each op handles layout preparation (weight packing, padding, transposes,
zero points, dequantization scales) and exposes a ``use_kernel`` switch:
``True`` runs the Pallas kernel — the Pallas interpreter on CPU (the
tests), Mosaic-compiled on a TPU (``tests/test_tpu_compile.py``
compiles the main-path kernels for a v5e at real widths;
``chip_smoke.py`` runs them on the chip) — ``False`` runs an
equivalent pure-jnp path, the form the model layer lowers in the
multi-pod dry-run, where XLA owns the fusion.

Dispatch table for ``packed_matmul`` (mode -> kernel -> constraints):

  mode           kernel                      weight format      constraints
  -------------  --------------------------  -----------------  ------------------------------
  sdv_matmul     kernels/sdv_matmul (GEMM,   SDV storage words  integer x; ``plan`` given;
                 grid R/br x G/bg x K/bk)    [K, G] int32, or   ``plan.spec.exact_wrap``;
                                             [2, K, G] limb     rows > GEMV_MAX_ROWS in auto
                                             planes (wide
                                             DSP48E2/DSP58
                                             words)
  sdv_matvec     kernels/sdv_matvec (GEMV,   SDV storage words  integer x; ``plan`` given;
                 grid B/bb x G/bg x K/bk)    [K, G] int32 /     same word gates as sdv_matmul;
                                             [2, K, G] planes   signed-element storage only;
                                                                rows <= GEMV_MAX_ROWS in auto
  quant_matmul   kernels/quant_matmul        lane words         float x; no ``plan`` (memory
                 (memory-packed, dequant     [K, N/(32/w)]      packing only); ``scale`` and
                 in-kernel)                  int32 + scale      ``w_bits`` given
  ref            pure jnp (XLA owns fusion)  either             always available; selected in
                                                                auto when ``use_kernel`` is
                                                                False, the datapath is not
                                                                exact-wrap (fp32m rounds, so
                                                                SDV spill tracking is invalid),
                                                                or a hand-built plan's layout
                                                                overruns its own storage word

``mode="auto"`` picks the first row that satisfies its constraints, in
the order ref-conditions -> sdv_matvec/sdv_matmul (by batch rows) ->
quant_matmul (no plan).  Explicit modes raise ``ValueError`` when their
constraints cannot be met rather than silently falling back.  Both
route selectors take ``explain=True`` to also return the *reason* for
the decision — the planner cost model (``repro.planner.cost``) and the
serve-time fallback log are built on it.  The SDV rows take the blocks
``sdv_blocks`` sizes from the call's shape unless the caller gives
them.

Dispatch table for ``packed_conv2d`` (mode -> kernel -> constraints):

  mode           kernel                      constraints
  -------------  --------------------------  ------------------------------
  bseg_conv2d    kernels/bseg_conv2d         integer x; BSEG ``plan`` on
                 (cross-channel batched      any datapath — the kernel
                 conv2d, grid B x H/bh x     body is word-generic (1-limb
                 C_out/bco, fused (kh,C_in)  int32 / fp32, or 2-limb int32
                 pipeline axis, row block    for the wide DSP48E2/DSP58
                 from ``bseg_blocks``)       words, per
                                             ``bseg_common.WordSpec``);
                                             stride 1, 'same' pad: odd kh
                                             and kw; ``plan.w_i <= 7``
  bseg_conv1d    kernels/bseg_conv1d         depthwise shape only
                 (depthwise, channels on     (C_in == 1, kh == 1, C_out
                 the VPU lanes)              == x channels); same plan
                                             constraints
  im2col         kernels/sdv_matmul via      integer x; patches unfolded
                 ``packed_matmul`` (SDV      in jnp, compute on the SDV
                 plan derived from the       datapath (exact-wrap words
                 BSEG widths: signed         only); odd kh and kw
                 w_i+1-bit activations —
                 or a planner-chosen
                 ``sdv_plan`` override)
  ref            pure jnp integer conv       always available; selected
                 (XLA owns the fusion)       in auto when ``use_kernel``
                                             is False, a hand-built
                                             plan's accumulation overruns
                                             the storage word, or
                                             ``plan.w_i > 7`` (the
                                             kernels stage activations
                                             in int8)

``mode="auto"`` routes ref-conditions -> bseg_conv1d (depthwise shape)
-> im2col (1x1 kernels on single-limb-word datapaths — a conv with no
spatial reuse is a GEMM) -> bseg_conv2d (everything else, including
1x1 on fp32m / dsp48e2 / dsp58 words, whose derived SDV GEMM would
need the wider storage layout).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.core import bseg as core_bseg
from repro.core import limbs as limb_ops
from repro.core import signed_split
from repro.core.datapath import BSEGPlan, SDVPlan
from . import bseg_common
from . import bseg_conv1d as bseg_kernel
from . import quant_matmul as qmm_kernel
from . import packbits
from . import ref


def _on_cpu() -> bool:
    return jax.default_backend() == "cpu"


# ---------------------------------------------------------------------------
# packbits
# ---------------------------------------------------------------------------

def pack_weights(w_int: jnp.ndarray, *, w: int,
                 use_kernel: bool = False) -> jnp.ndarray:
    """Dense [m, n] ints -> [m, n/(32/w)] int32 lane words."""
    if use_kernel:
        return packbits.pack_words(w_int.astype(jnp.int8), w=w,
                                   interpret=_on_cpu())
    return ref.pack_words_ref(w_int, w=w)


def unpack_weights(packed: jnp.ndarray, *, w: int,
                   use_kernel: bool = False) -> jnp.ndarray:
    if use_kernel:
        return packbits.unpack_words(packed, w=w, interpret=_on_cpu())
    return ref.unpack_words_ref(packed, w=w)


# ---------------------------------------------------------------------------
# quant_matmul  (packed_memory execution mode)
# ---------------------------------------------------------------------------

def quant_matmul(x: jnp.ndarray, w_packed: jnp.ndarray, scale: jnp.ndarray,
                 *, w: int, use_kernel: bool = True,
                 block_m: int = 128, block_n: int = 1024,
                 block_k: int = 512) -> jnp.ndarray:
    """x [m, k] @ dequant(w_packed [k, n/(32/w)]) -> [m, n] f32.

    A block that does not divide ``n`` (or ``k``) widens to the whole
    axis, which is always a legal TPU block."""
    if use_kernel:
        n, k = w_packed.shape[1] * (32 // w), x.shape[-1]
        block_n = block_n if n % min(block_n, n) == 0 else n
        block_k = block_k if k % min(block_k, k) == 0 else k
        return qmm_kernel.quant_matmul(
            x, w_packed, scale, w=w, bm=block_m, bn=block_n, bk=block_k,
            interpret=_on_cpu())
    w_int = ref.unpack_words_ref(w_packed.reshape(-1, w_packed.shape[-1]),
                                 w=w).reshape(w_packed.shape[0], -1)
    return ref.quant_matmul_ref(x, w_int, scale)


# ---------------------------------------------------------------------------
# sdv_matvec  (packed_compute_sdv execution mode)
# ---------------------------------------------------------------------------

def prepare_sdv_weights(w_int: jnp.ndarray, plan: SDVPlan) -> jnp.ndarray:
    """[M, K] ints (w_a-bit, signedness per ``plan.signed_a``) -> [K, G]
    storage words in the plan's transport layout
    (``bseg_common.sdv_word_spec``) — one int32 array for plans whose
    layout fits 32 bits, two int32 limb planes ([2, K, G]) for the wide
    DSP48E2/DSP58 words (fields past bit 31 live in the hi limb; no
    int64, no ``jax_enable_x64``).

    Signed layout: sign-sliced remainder fields (D) in the low
    ``plan.packed_width`` bits, the n sign bits parked above — the two
    pre-adder operands in one word.  Unsigned layout: the values sit
    directly in their lanes (no pre-adder needed).
    """
    m, k = w_int.shape
    n = plan.n
    g = -(-m // n)
    ws = bseg_common.sdv_word_spec(plan)
    wp = jnp.pad(w_int, ((0, g * n - m), (0, 0))).reshape(g, n, k)
    if ws.limbs == 2:
        wp32 = wp.astype(jnp.int32)
        if plan.signed_a:
            # SDV storage is the D word (sign-sliced remainders in
            # their lanes) with the raw sign bits parked above the
            # packed field — NOT the pre-adder difference, which the
            # kernel materializes per step.
            r, s = signed_split.split_signed(wp32, plan.w_a)
            word = signed_split.pack_unsigned_limbs(
                jnp.moveaxis(r, 1, -1), plan.w_a, plan.lane)  # [G, K]
            for i in range(n):
                word = limb_ops.bit_or(
                    word,
                    limb_ops.shift_left(limb_ops.from_u32(s[:, i, :]),
                                        plan.packed_width + i))
        else:
            word = signed_split.pack_unsigned_limbs(
                jnp.moveaxis(wp32, 1, -1), plan.w_a, plan.lane)
        planes = limb_ops.stack_planes(word)                 # [2, G, K]
        return jnp.swapaxes(planes, 1, 2)                    # [2, K, G]
    wdt = ws.dtype
    word = jnp.zeros((g, k), wdt)
    if plan.signed_a:
        r, s = signed_split.split_signed(wp.astype(wdt), plan.w_a)
        for i in range(n):
            word = word | (r[:, i, :].astype(wdt) << (i * plan.lane))
            word = word | (s[:, i, :].astype(wdt)
                           << (plan.packed_width + i))
    else:
        for i in range(n):
            word = word | (wp[:, i, :].astype(wdt) << (i * plan.lane))
    return word.T                                           # [K, G]


#: Vregs of one ``[br, bg]`` int32 array that a K step of the SDV
#: GEMM/GEMV works on, at most (``sdv_blocks``).  On a v5e, a 16-row
#: granite-8b layer takes 24% longer at 8 and 3.5% less at 32: past 16
#: the loop's carried arrays outgrow the vreg file and the cost per
#: vreg doubles (``scripts/sdv_tile_sweep.py``; PERF.md section 6).
SDV_TILE_VREGS = 16
#: Double-buffered storage-word block the tile rule stays within
#: (v5e's scoped VMEM is 16 MiB; the rest holds activations, the
#: accumulator and the outputs).
SDV_WEIGHT_VMEM_BYTES = 8 << 20
#: Most ragged-G padding, as a share of G, that a wider group block
#: may add; past it the rule takes more, narrower blocks.
SDV_MAX_G_PAD = 0.15


def sdv_blocks(rows: int, k: int, g: int, plan: SDVPlan, *,
               block_rows: int = 128,
               block_g: Optional[int] = None,
               block_k: Optional[int] = None,
               target_vregs: int = SDV_TILE_VREGS) -> tuple[int, int, int]:
    """The SDV kernel's ``(br, bg, bk)`` for an ``[rows, k] x [k, g]``
    word GEMM; a block given explicitly is kept.

    Each K step of the kernel runs one loop iteration over a
    ``[br, bg]`` tile at a fixed cost plus a cost per vreg (PERF.md
    section 5), so a thin row block takes a wide group block: G is
    spread evenly over the fewest 128-lane-multiple blocks that keep
    ``ceil(br/8) * ceil(bg/128)`` within ``target_vregs`` and add at
    most ``SDV_MAX_G_PAD`` ragged padding; a full 128-row block takes
    one 128-lane group block.  ``bk`` is the first of ``min(K, 512)``,
    256 and 128 that divides K and whose double-buffered word block
    fits ``SDV_WEIGHT_VMEM_BYTES``, else all of K; an explicit
    ``block_k`` that does not divide K is all of K too.
    """
    br = min(rows, block_rows)
    if block_g is None:
        tiles = -(-g // 128)
        max_tiles = max(1, target_vregs // -(-br // 8))
        for nblocks in range(-(-tiles // max_tiles), tiles + 1):
            block_g = 128 * -(-tiles // nblocks)
            if block_g >= g:
                block_g = g
                break
            if -(-g // block_g) * block_g - g <= SDV_MAX_G_PAD * g:
                break
    if block_k is None:
        limbs = bseg_common.sdv_word_spec(plan).limbs
        block_k = k
        for bk in (min(k, 512), 256, 128):
            if (k % bk == 0 and limbs * bk * min(block_g, g) * 4 * 2
                    <= SDV_WEIGHT_VMEM_BYTES):
                block_k = bk
                break
    else:
        block_k = min(block_k, k)
        if k % block_k:
            block_k = k  # fall back to a single K block
    return br, block_g, block_k


#: Vregs of one ``[khc, bh*bco]`` word that a step of the BSEG conv2d
#: kernel works on, at most (``bseg_blocks``): on a v5e a stage's time
#: stops falling at about this size (PERF.md section 6).
BSEG_TILE_VREGS = 64
#: The double-buffered input, factor and output blocks of a BSEG conv2d
#: program stay within this (v5e's scoped VMEM is 16 MiB).
BSEG_VMEM_BYTES = 10 << 20


def bseg_blocks(h_out: int, w_pad: int, khc: int, bco: int, n_groups: int,
                plan: BSEGPlan) -> int:
    """The BSEG conv2d kernel's row block ``bh``: output rows that one
    grid program advances together through every step.

    A step costs a fixed amount plus an amount per vreg of its words
    (PERF.md section 5), and a block of rows pays the fixed part once.
    The word of ``bh`` rows is ``[khc, bh*bco]``: ``ceil(khc/8) *
    ceil(bh*bco/128)`` vregs per limb.  ``bh`` is the most rows whose
    word stays within ``BSEG_TILE_VREGS`` (or within one row's word, where
    that is larger) and whose blocks fit ``BSEG_VMEM_BYTES``: the int8
    ``[W_pad, bh, khc]`` input, the ``[n_groups, khc, bh*bco]`` factors
    and the int32 ``[W_pad, bh*bco]`` output, each double-buffered.  H is
    then spread evenly over the fewest blocks of at most that many rows.
    """
    limbs = bseg_common.word_spec(plan).limbs

    def lanes(bh):
        return -(-(bh * bco) // 128) * 128

    def vregs(bh):
        return limbs * -(-khc // 8) * lanes(bh) // 128

    def vmem(bh):
        x_block = w_pad * -(-bh // 32) * 32 * -(-khc // 128) * 128
        kap_block = limbs * n_groups * -(-khc // 8) * 8 * lanes(bh) * 4
        return 2 * (x_block + kap_block + w_pad * lanes(bh) * 4)

    cap = max(BSEG_TILE_VREGS, vregs(1))
    bh = 1
    while (bh < h_out and vregs(bh + 1) <= cap
           and vmem(bh + 1) <= BSEG_VMEM_BYTES):
        bh += 1
    return -(-h_out // -(-h_out // bh))


def sdv_matvec(x_q: jnp.ndarray, w_words: jnp.ndarray, *, plan: SDVPlan,
               m: int, use_kernel: bool = True,
               block_b: int = 8, block_g: Optional[int] = None,
               block_k: Optional[int] = None) -> jnp.ndarray:
    """Batched exact integer GEMV through the SDV datapath.

    x_q: [B, K] int8 activations, w_words: [K, G] from
    ``prepare_sdv_weights``; returns [B, m] int32.  Blocks left
    ``None`` come from ``sdv_blocks``.
    """
    from . import sdv_matvec as sdv_kernel
    b, k = x_q.shape
    if use_kernel:
        bb, bg, bk = sdv_blocks(b, k, w_words.shape[-1], plan,
                                block_rows=block_b, block_g=block_g,
                                block_k=block_k)
        lanes = sdv_kernel.sdv_matvec(
            x_q.T, w_words, plan=plan, bb=bb, bg=bg, bk=bk,
            interpret=_on_cpu())                            # [B, G, n]
        return lanes.reshape(b, -1)[:, :m]
    # pure-jnp path: unpack words back to ints and do the exact GEMV
    w_int = ref.sdv_unpack_words_ref(w_words, plan=plan)     # [K, M_pad]
    y = ref.sdv_matvec_ref(x_q, w_int.T)
    return y[:, :m]


# ---------------------------------------------------------------------------
# packed_matmul  (dispatch layer — see the module docstring table)
# ---------------------------------------------------------------------------

#: ``mode="auto"`` routes row counts up to this through the GEMV kernel
#: (its row blocks are sized for decode micro-batches); anything larger
#: takes the blocked GEMM kernel.
GEMV_MAX_ROWS = 8

_PACKED_MODES = ("auto", "sdv_matmul", "sdv_matvec", "quant_matmul", "ref")


def _matmul_word_gate(plan: SDVPlan) -> Optional[str]:
    """Why the SDV GEMM/GEMV kernels cannot represent this plan's word,
    or ``None`` when they can.

    The kernels are word-generic (``bseg_common.sdv_word_spec``): one
    int32 limb for layouts that fit the 32-bit TPU lane, two
    carry-propagating int32 limbs for the wide DSP48E2/DSP58 words —
    both compile on any backend with int32, so datapath width no
    longer gates the route.  The only remaining word gate: a
    hand-built plan whose storage layout (packed field + parked sign
    bits) overruns its own datapath word is rejected, so it degrades
    to lossless ref / raises instead of tripping a kernel assert.
    """
    layout_bits = bseg_common.sdv_layout_bits(plan)
    if layout_bits > plan.spec.w_word:
        return (f"plan overruns the {plan.spec.name} storage word: "
                f"packed field + parked sign bits = {layout_bits} bits "
                f"> w_word={plan.spec.w_word}")
    return None


def select_packed_route(rows: int, *, plan: Optional[SDVPlan] = None,
                        use_kernel: bool = True, mode: str = "auto",
                        explain: bool = False):
    """Pick the kernel for a packed matmul (the module-docstring table).

    Pure function of (batch rows, bitwidth plan, backend capability) so
    the routing itself is testable without running any kernel.  With
    ``explain=True`` returns ``(route, reason)`` instead of the bare
    route name — the reason string says why the route was chosen, which
    is what the planner cost model penalizes (a ref fallback means the
    plan never reaches the packed datapath).
    """
    def _r(route: str, reason: str):
        return (route, reason) if explain else route

    if mode not in _PACKED_MODES:
        raise ValueError(f"unknown packed_matmul mode {mode!r}")
    if mode in ("sdv_matmul", "sdv_matvec"):
        if plan is None:
            raise ValueError(f"mode {mode!r} needs an SDVPlan")
        if not plan.spec.exact_wrap:
            raise ValueError(
                f"mode {mode!r} needs exact-wrap arithmetic; datapath "
                f"{plan.spec.name} rounds (fp32)")
        gate = _matmul_word_gate(plan)
        if gate is not None:
            raise ValueError(f"mode {mode!r}: {gate}")
        if mode == "sdv_matvec" and not plan.signed_a:
            raise ValueError(
                "the GEMV kernel stores signed elements only (parked "
                "sign bits); use sdv_matmul for unsigned plans")
        return _r(mode, "explicitly requested")
    if mode == "quant_matmul":
        if plan is not None:
            raise ValueError(
                "mode 'quant_matmul' takes memory-packed lane words, "
                "not an SDV plan")
        return _r(mode, "explicitly requested")
    if mode == "ref":
        return _r(mode, "explicitly requested")
    # --- auto ---
    if plan is None:
        if use_kernel:
            return _r("quant_matmul",
                      "no SDV plan: memory-packed lane words")
        return _r("ref", "no Pallas backend (use_kernel=False)")
    if not use_kernel:
        return _r("ref", "no Pallas backend (use_kernel=False)")
    if not plan.spec.exact_wrap:
        return _r("ref", f"datapath {plan.spec.name} rounds (fp32): "
                         "SDV spill-over tracking is invalid")
    gate = _matmul_word_gate(plan)
    if gate is not None:
        return _r("ref", gate)
    if rows <= GEMV_MAX_ROWS and plan.signed_a:
        return _r("sdv_matvec",
                  f"{rows} rows <= GEMV_MAX_ROWS={GEMV_MAX_ROWS}: "
                  "decode-micro-batch GEMV blocks")
    if rows <= GEMV_MAX_ROWS:
        return _r("sdv_matmul",
                  "unsigned elements: the GEMV kernel stores signed "
                  "elements only")
    return _r("sdv_matmul",
              f"{rows} rows > GEMV_MAX_ROWS={GEMV_MAX_ROWS}: "
              "blocked batched GEMM")


def packed_matmul(x: jnp.ndarray, w: jnp.ndarray, *,
                  plan: Optional[SDVPlan] = None, m: Optional[int] = None,
                  scale: Optional[jnp.ndarray] = None,
                  w_bits: Optional[int] = None,
                  mode: str = "auto", use_kernel: bool = True,
                  block_rows: int = 128, block_g: Optional[int] = None,
                  block_k: Optional[int] = None) -> jnp.ndarray:
    """Batched packed matmul with kernel dispatch.

    Args:
      x: activations ``[..., K]`` — integer (within ``plan.w_b`` bits)
        for the SDV routes, float for the memory-packed route.
      w: SDV storage words ``[K, G]`` when ``plan`` is given, else
        memory-packed lane words ``[K, N/(32/w_bits)]``.
      plan: SDV lane plan; ``None`` selects the memory-packed side of
        the table.
      m: real output-channel count (trims the ``G*n`` lane padding);
        defaults to all lanes.
      scale / w_bits: dequantization scale ``[N]`` and element width —
        required by the ``quant_matmul`` route only.
      mode: a row of the dispatch table, or ``"auto"``.
      block_rows / block_g / block_k: kernel blocks; on the SDV routes
        a ``None`` block comes from ``sdv_blocks``.

    Returns:
      ``[..., M]`` — int32 (exact) on the SDV/ref integer routes, f32
      on the memory-packed route.
    """
    batch_shape, k = x.shape[:-1], x.shape[-1]
    x2 = x.reshape(-1, k)
    route = select_packed_route(
        x2.shape[0], plan=plan, use_kernel=use_kernel, mode=mode)

    if plan is None:  # memory-packed lane words (kernel or jnp ref)
        if scale is None or w_bits is None:
            raise ValueError(f"route {route!r} needs scale and w_bits")
        y = quant_matmul(x2, w, scale, w=w_bits,
                         use_kernel=(route == "quant_matmul"),
                         block_m=block_rows, block_k=block_k or 512)
        y = y if m is None else y[:, :m]
        return y.reshape(batch_shape + y.shape[-1:])

    if not jnp.issubdtype(x.dtype, jnp.integer):
        # float activations would be silently truncated by the integer
        # datapath — quantize to w_b bits first (models/quantized.py
        # sdv_matmul_apply) or use the memory-packed route
        raise ValueError(
            f"route {route!r} needs integer activations within "
            f"plan.w_b={plan.w_b} bits, got {x.dtype}")

    g = w.shape[-1]
    m = g * plan.n if m is None else m
    if route == "ref":
        w_int = ref.sdv_unpack_words_ref(w, plan=plan)       # [K, M_pad]
        y = ref.sdv_matmul_ref(x2, w_int.T)[:, :m]
        return y.reshape(batch_shape + (m,))

    if route == "sdv_matvec":
        y = sdv_matvec(x2.astype(jnp.int32), w, plan=plan, m=m,
                       use_kernel=True, block_g=block_g, block_k=block_k)
        return y.reshape(batch_shape + (m,))

    # sdv_matmul
    from . import sdv_matmul as sdvmm_kernel
    br, bg, bk = sdv_blocks(x2.shape[0], k, g, plan, block_rows=block_rows,
                            block_g=block_g, block_k=block_k)
    lanes = sdvmm_kernel.sdv_matmul(x2.astype(jnp.int32), w, plan=plan,
                                    br=br, bg=bg, bk=bk,
                                    interpret=_on_cpu())     # [R, G, n]
    y = lanes.reshape(x2.shape[0], -1)[:, :m]
    return y.reshape(batch_shape + (m,))


# ---------------------------------------------------------------------------
# bseg_conv1d  (packed_compute_bseg execution mode)
# ---------------------------------------------------------------------------

def prepare_bseg_taps(taps: jnp.ndarray, plan: BSEGPlan):
    """[C, n] signed taps -> (packed factors in the plan's transport
    layout, [C] tap sums).

    Single-limb plans store [G, C] words in the plan's word dtype; wide
    (2-limb) plans store [2, G, C] int32 limb planes
    (``core.limbs``) — no int64, no ``jax_enable_x64``.

    Tap groups are packed reversed through the pre-adder; the tap sums
    feed the zero-point correction.
    """
    c, n = taps.shape
    groups = -(-n // plan.n_k)
    tp = jnp.pad(taps, ((0, 0), (0, groups * plan.n_k - n)))
    ws = bseg_common.word_spec(plan)
    kappas = []
    for gi in range(groups):
        seg = tp[:, gi * plan.n_k:(gi + 1) * plan.n_k]
        if ws.limbs == 2:
            word = signed_split.pack_signed_limbs(
                seg[:, ::-1].astype(jnp.int32), plan.w_k, plan.lane)
            kappas.append(limb_ops.stack_planes(word))       # [2, C]
        else:
            kappas.append(core_bseg.bseg_pack_kernel(seg, plan)
                          .astype(ws.dtype))
    kappa = jnp.stack(kappas, axis=1 if ws.limbs == 2 else 0)
    return kappa, jnp.sum(taps.astype(jnp.int32), axis=-1)


def bseg_conv1d(x_q: jnp.ndarray, kappa: jnp.ndarray, tap_sum: jnp.ndarray,
                *, plan: BSEGPlan, n_taps: int, zero_point: int = 0,
                padding: str = "causal",
                use_kernel: bool = True) -> jnp.ndarray:
    """Depthwise conv1d: x_q [B, S, C] int8 (signed, zero_point shifts
    it to the unsigned datapath domain); returns [B, S, C] i32.

    ``padding="causal"`` aligns output s with inputs s-n+1..s (decode
    convs); ``"same"`` centers the window (the conv2d depthwise route).
    """
    b, s, c = x_q.shape
    n = n_taps
    ws = bseg_common.word_spec(plan)
    n_groups = kappa.shape[1] if ws.limbs == 2 else kappa.shape[0]
    if padding not in ("causal", "same"):
        raise ValueError(f"unknown padding {padding!r}")
    left = n - 1 if padding == "causal" else (n - 1) // 2
    if not use_kernel:
        taps = _unpack_bseg_taps(kappa, plan, n)
        return ref.conv1d_ref(x_q, taps, left)
    xu = (x_q.astype(jnp.int32) + zero_point).astype(jnp.int8)
    n_steps = -(-(s + plan.n_k - 1) // plan.n_i)
    need = (n_steps - 1) * plan.n_i + (n_groups - 1) * plan.n_k + plan.n_i
    # the boundary pad is signed-zero, i.e. the *zero point* in the
    # unsigned datapath domain (the uniform zp*sum(taps) correction then
    # holds at the boundary too); extra right pad only feeds discarded
    # outputs.
    x_pad = jnp.pad(xu, ((0, 0), (left, max(0, need - (s + left))), (0, 0)),
                    constant_values=zero_point)
    y = bseg_kernel.bseg_conv1d(x_pad, kappa, plan=plan, s_out=s,
                                interpret=_on_cpu())
    if zero_point:
        y = y - zero_point * tap_sum[None, None, :]
    return y


# ---------------------------------------------------------------------------
# packed_conv2d  (dispatch layer — see the module docstring table)
# ---------------------------------------------------------------------------

_CONV_MODES = ("auto", "bseg_conv2d", "bseg_conv1d", "im2col", "ref")


def _conv_word_gate(plan: BSEGPlan) -> Optional[str]:
    """Why the BSEG conv kernels cannot represent this plan's word, or
    ``None`` when they can.

    The kernels are datapath-generic (``bseg_common.WordSpec``): one
    int32 limb for the INT32 lane, float32 for FP32M (guard-bit
    dimensioning keeps every intermediate exact), two carry-propagating
    int32 limbs for the wide DSP48E2/DSP58 words — so every planner
    plan compiles on any backend with int32 (no ``jax_enable_x64``, no
    interpret-only gate).  The only remaining gate is a hand-built plan
    whose biased accumulation word overruns the accumulator
    (``plan_bseg`` refuses to dimension these): it is rejected here so
    it degrades to ref / raises instead of tripping a kernel-internal
    assert.
    """
    if plan.n_lanes * plan.lane > plan.spec.w_word:
        return (f"plan overruns the {plan.spec.name} accumulator word: "
                f"{plan.n_lanes} lanes x L={plan.lane} > "
                f"w_word={plan.spec.w_word} (the top lane's guard bias "
                "falls off the word)")
    return None


def _sdv_words_int32(spec) -> bool:
    """True when the SDV GEMM stores this datapath's words in a single
    int32 limb — the *auto* route's preference for the im2col GEMM.
    2-limb SDV words compile too (explicit ``mode="im2col"`` takes
    them), but the BSEG kernels run wide words with fewer limb ops per
    MAC, so auto keeps 1x1 convs on the BSEG datapath there."""
    return spec.exact_wrap and spec.w_word <= 32


def prepare_bseg_conv2d(w_int: jnp.ndarray, plan: BSEGPlan):
    """[C_out, C_in, kh, kw] signed taps -> (packed kernel-row factors
    in the plan's transport layout, [C_out] tap sums).

    Single-limb plans store [G, kh, C_in, C_out] words in the plan's
    word dtype; wide (2-limb) plans store [2, G, kh, C_in, C_out]
    int32 limb planes (``core.limbs``).

    Each kernel row of each (C_out, C_in) pair packs its kw taps into
    ceil(kw/n_k) groups, reversed through the pre-adder; the tap sums
    feed the zero-point correction.
    """
    c_out, c_in, kh, kw = w_int.shape
    groups = -(-kw // plan.n_k)
    wp = jnp.pad(w_int, ((0, 0), (0, 0), (0, 0),
                         (0, groups * plan.n_k - kw)))
    ws = bseg_common.word_spec(plan)
    kappas = []
    for gi in range(groups):
        seg = wp[..., gi * plan.n_k:(gi + 1) * plan.n_k]
        if ws.limbs == 2:
            word = signed_split.pack_signed_limbs(
                seg[..., ::-1].astype(jnp.int32), plan.w_k, plan.lane)
            kappas.append(limb_ops.stack_planes(word))  # [2, C_out, C_in, kh]
        else:
            kappas.append(core_bseg.bseg_pack_kernel(seg, plan)
                          .astype(ws.dtype))
    if ws.limbs == 2:
        kappa = jnp.stack(kappas, axis=1)        # [2, G, C_out, C_in, kh]
        kappa = jnp.transpose(kappa, (0, 1, 4, 3, 2))
    else:
        kappa = jnp.stack(kappas, axis=0)        # [G, C_out, C_in, kh]
        kappa = jnp.transpose(kappa, (0, 3, 2, 1))
    tap_sum = jnp.sum(w_int.astype(jnp.int32), axis=(1, 2, 3))
    return kappa, tap_sum


def _is_depthwise(x_shape, w_shape) -> bool:
    c_out, c_in, kh, _ = w_shape
    return c_in == 1 and kh == 1 and c_out == x_shape[-1]


def select_conv_route(x_shape, w_shape, *, plan: BSEGPlan,
                      use_kernel: bool = True, mode: str = "auto",
                      explain: bool = False):
    """Pick the kernel for a packed conv2d (the module-docstring table).

    Pure function of (activation shape, weight shape, bitwidth plan,
    backend capability) so the routing is testable without running any
    kernel.  ``x_shape`` is [B, H, W, C_in]; ``w_shape`` is [C_out,
    C_in, kh, kw].  With ``explain=True`` returns ``(route, reason)``
    — see ``select_packed_route``.
    """
    def _r(route: str, reason: str):
        return (route, reason) if explain else route

    if mode not in _CONV_MODES:
        raise ValueError(f"unknown packed_conv2d mode {mode!r}")
    c_out, c_in, kh, kw = w_shape
    if x_shape[-1] != c_in and not _is_depthwise(x_shape, w_shape):
        raise ValueError(
            f"activation channels {x_shape[-1]} != weight C_in {c_in}")
    if mode in ("bseg_conv2d", "bseg_conv1d", "im2col"):
        if mode == "im2col":
            if not plan.spec.exact_wrap:
                raise ValueError(
                    "mode 'im2col' computes on the SDV datapath, which "
                    f"needs exact-wrap arithmetic; {plan.spec.name} "
                    "rounds (fp32) — use the bseg kernels instead")
        else:
            gate = _conv_word_gate(plan)
            if gate is not None:
                raise ValueError(f"mode {mode!r}: {gate}")
        if plan.w_i > 7:
            raise ValueError(
                f"mode {mode!r} stages activations in int8: plan.w_i "
                f"must be <= 7, got {plan.w_i}")
        if kh % 2 == 0 or kw % 2 == 0:
            raise ValueError(
                f"mode {mode!r} is stride-1 'same' pad: kh/kw must be "
                f"odd, got {kh}x{kw}")
        if mode == "bseg_conv1d" and not _is_depthwise(x_shape, w_shape):
            raise ValueError(
                "mode 'bseg_conv1d' needs a depthwise shape: C_in == 1, "
                f"kh == 1, C_out == activation channels; got w {w_shape} "
                f"on x {tuple(x_shape)}")
        return _r(mode, "explicitly requested")
    if mode == "ref":
        return _r(mode, "explicitly requested")
    # --- auto ---
    if not use_kernel:
        return _r("ref", "no Pallas backend (use_kernel=False)")
    gate = _conv_word_gate(plan)
    if gate is not None:
        return _r("ref", gate)
    if plan.w_i > 7:
        return _r("ref", f"plan.w_i={plan.w_i} > 7: the conv kernels "
                         "stage activations in int8")
    if kh % 2 == 0 or kw % 2 == 0:
        return _r("ref", f"even kernel {kh}x{kw}: no stride-1 'same' "
                         "pad")
    if _is_depthwise(x_shape, w_shape):
        return _r("bseg_conv1d",
                  f"depthwise shape on the {plan.spec.name} word: "
                  "channels ride the VPU lanes")
    if kh == 1 and kw == 1:
        if _sdv_words_int32(plan.spec):
            return _r("im2col", "1x1 kernel: no spatial reuse -> GEMM "
                                "on the SDV datapath")
        return _r("bseg_conv2d",
                  f"1x1 kernel on the wide {plan.spec.name} word: the "
                  "2-limb SDV GEMM pays extra limb ops per MAC, the "
                  "BSEG kernel runs the wide word natively")
    return _r("bseg_conv2d",
              f"dense kxk conv on the {plan.spec.name} word: one "
              "cross-channel kernel launch")


def select_conv1d_route(plan: BSEGPlan, *, use_kernel: bool = True,
                        explain: bool = False):
    """Route for the *causal* depthwise short conv (``bseg_conv1d``
    called directly, e.g. the ``BSEGConv`` serving container): no
    odd-taps 'same'-pad constraint, only the datapath gates.  Shares
    the gate conditions with ``select_conv_route`` so the planner cost
    model and the dispatch can never disagree."""
    def _r(route: str, reason: str):
        return (route, reason) if explain else route

    if not use_kernel:
        return _r("ref", "no Pallas backend (use_kernel=False)")
    gate = _conv_word_gate(plan)
    if gate is not None:
        return _r("ref", gate)
    if plan.w_i > 7:
        return _r("ref", f"plan.w_i={plan.w_i} > 7: the conv kernels "
                         "stage activations in int8")
    return _r("bseg_conv1d",
              f"causal depthwise short conv on the {plan.spec.name} word")


def _im2col_sdv_plan(plan: BSEGPlan) -> SDVPlan:
    """SDV plan matching the BSEG widths for the im2col route: signed
    w_k-bit taps against signed (w_i+1)-bit activations — wide enough
    for the unsigned w_i datapath domain AND the signed pre-shift
    values, so no zero-point handling is needed on this route."""
    from repro.core.datapath import plan_sdv
    return plan_sdv(plan.spec, plan.w_k, plan.w_i + 1, signed_a=True,
                    signed_b=True, park_sign_bits=True)


def _im2col_patches(x32: jnp.ndarray, kh: int, kw: int) -> jnp.ndarray:
    """[B, H, W, C] ints -> [B, H, W, kh*kw*C] 'same'-pad patches."""
    if kh == 1 and kw == 1:
        return x32
    b, h, w, c = x32.shape
    xp = jnp.pad(x32, ((0, 0), (kh // 2, kh // 2), (kw // 2, kw // 2),
                       (0, 0)))
    cols = [xp[:, r:r + h, q:q + w, :]
            for r in range(kh) for q in range(kw)]
    return jnp.concatenate(cols, axis=-1)


def packed_conv2d(x: jnp.ndarray, w_int: jnp.ndarray, *, plan: BSEGPlan,
                  mode: str = "auto", zero_point: int = 0,
                  use_kernel: bool = True, block_co: int = 128,
                  block_h: Optional[int] = None,
                  sdv_plan: Optional[SDVPlan] = None) -> jnp.ndarray:
    """Stride-1 'same'-pad conv2d with kernel dispatch.

    Args:
      x: [B, H, W, C_in] integer activations; ``x + zero_point`` must
        lie in the unsigned datapath domain [0, 2^w_i) (pass 0 when the
        activations are already unsigned, e.g. post-requantization).
      w_int: [C_out, C_in, kh, kw] signed taps within ``plan.w_k`` bits.
      plan: BSEG plan on any supported datapath (the kernels run the
        word in its native representation — int32 / fp32 / two int32
        limb planes for the wide DSP words).
      mode: a row of the dispatch table, or ``"auto"``.
      block_co: output-channel block size for the conv2d kernel
        (downgraded to C_out when not divisible).
      block_h: output rows per conv2d kernel program; ``None`` takes
        ``bseg_blocks``'s choice for the shape.
      sdv_plan: optional SDV plan for the im2col route (the planner
        picks one per layer); defaults to the plan derived from the
        BSEG widths.  An unsigned-element-domain override
        (``signed_b=False``) is only valid with ``zero_point == 0``
        (the pre-shift signed values would leave the domain).

    Returns:
      [B, H, W, C_out] int32 — the exact signed-domain correlation
      (identical to ``ref.conv2d_int_ref`` on every route).
    """
    if not jnp.issubdtype(x.dtype, jnp.integer):
        raise ValueError(
            f"packed_conv2d needs integer activations within "
            f"plan.w_i={plan.w_i} bits (+zero_point), got {x.dtype}")
    if sdv_plan is not None and not sdv_plan.signed_b and zero_point:
        raise ValueError(
            "an unsigned-multiplier sdv_plan needs zero_point == 0: "
            "the im2col route feeds the pre-shift signed activations")
    route = select_conv_route(x.shape, w_int.shape, plan=plan,
                              use_kernel=use_kernel, mode=mode)
    b, h, w, c_in = x.shape
    c_out, _, kh, kw = w_int.shape

    if route == "ref":
        return ref.conv2d_int_ref(x, w_int)

    if route == "bseg_conv1d":
        taps = w_int[:, 0, 0, :]                             # [C, kw]
        kappa, tap_sum = prepare_bseg_taps(taps, plan)
        y = bseg_conv1d(x.reshape(b * h, w, c_in).astype(jnp.int8), kappa,
                        tap_sum, plan=plan, n_taps=kw,
                        zero_point=zero_point, padding="same",
                        use_kernel=True)
        return y.reshape(b, h, w, c_in)

    if route == "im2col":
        if sdv_plan is None:
            sdv_plan = _im2col_sdv_plan(plan)
        patches = _im2col_patches(x.astype(jnp.int32), kh, kw)
        w2 = w_int.astype(jnp.int32).transpose(0, 2, 3, 1) \
            .reshape(c_out, kh * kw * c_in)
        words = prepare_sdv_weights(w2, sdv_plan)
        return packed_matmul(patches, words, plan=sdv_plan, m=c_out,
                             use_kernel=True)

    # bseg_conv2d
    from . import bseg_conv2d as bseg2d_kernel
    kappa, tap_sum = prepare_bseg_conv2d(w_int, plan)
    ws = bseg_common.word_spec(plan)
    n_groups = kappa.shape[1] if ws.limbs == 2 else kappa.shape[0]
    n_steps = -(-(w + plan.n_k - 1) // plan.n_i)
    need = (n_steps - 1) * plan.n_i + (n_groups - 1) * plan.n_k + plan.n_i
    pad_h, pad_w = kh // 2, kw // 2
    xu = (x.astype(jnp.int32) + zero_point).astype(jnp.int8)
    # the boundary pad is signed-zero = the zero point in the unsigned
    # domain; extra right pad only feeds discarded outputs.
    x_pad = jnp.pad(
        xu, ((0, 0), (pad_h, pad_h),
             (pad_w, max(pad_w, need - (w + pad_w))), (0, 0)),
        constant_values=zero_point)
    bco = min(block_co, c_out)
    if c_out % bco:
        bco = c_out
    if block_h is None:
        block_h = bseg_blocks(h, x_pad.shape[2], kh * c_in, bco, n_groups,
                              plan)
    y = bseg2d_kernel.bseg_conv2d(x_pad, kappa, plan=plan, h_out=h,
                                  w_out=w, bh=min(block_h, h), bco=bco,
                                  interpret=_on_cpu())
    if zero_point:
        y = y - zero_point * tap_sum[None, None, None, :]
    return y


def _unpack_bseg_taps(kappa: jnp.ndarray, plan: BSEGPlan,
                      n_taps: int) -> jnp.ndarray:
    """Recover [C, n] signed taps from packed factors (test/fallback).

    Accepts either transport layout: [G, C] single words, or
    [2, G, C] int32 limb planes for the wide (2-limb) plans.
    """
    ws = bseg_common.word_spec(plan)
    groups = kappa.shape[1] if ws.limbs == 2 else kappa.shape[0]
    segs = []
    for gi in range(groups):
        vals = []
        if ws.limbs == 2:
            rem = limb_ops.from_planes(kappa[:, gi])
            # lanes hold the arithmetic sum; decode low-to-high with
            # borrow, in the mod-2^64 limb domain
            for i in range(plan.n_k):
                f = limb_ops.field(rem, i * plan.lane, plan.lane)
                sign = limb_ops.field(
                    rem, i * plan.lane + plan.lane - 1, 1).lo
                neg = limb_ops.sub(
                    f, limb_ops.full(sign.shape, 1 << plan.lane))
                v = jnp.where(sign == 1, neg.lo, f.lo)
                vals.append(v)
                rem = limb_ops.sub(rem, limb_ops.shift_left(
                    limb_ops.from_i32(v), i * plan.lane))
        else:
            # fp32m factors are exact integers below 2^24: int32 decode
            rem = kappa[gi].astype(jnp.int32)
            # lanes hold the arithmetic sum; decode low-to-high with borrow
            for i in range(plan.n_k):
                f = (rem >> (i * plan.lane)) & ((1 << plan.lane) - 1)
                v = jnp.where(f >= (1 << (plan.lane - 1)),
                              f - (1 << plan.lane), f)
                vals.append(v)
                rem = rem - (v << (i * plan.lane))
        seg = jnp.stack(vals[::-1], axis=-1)                 # un-reverse
        segs.append(seg)
    taps = jnp.concatenate(segs, axis=-1)[:, :n_taps]
    return taps.astype(jnp.int32)
