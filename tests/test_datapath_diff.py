"""Cross-datapath differential test harness.

The paper's central claim is that pre-adder packing works on *any* wide
datapath; this file is the executable version of that claim for the
dispatch layer:

  * ROUTE INVARIANTS (no kernels run): for every plan the planner can
    emit — every bit config x datapath x packing factor x guard bits x
    signedness — the dispatch route, the cost-model route and the
    explain reason must agree, and no implemented datapath may fall
    back to ref with an "unimplemented" reason.  This is the drift
    detector between ``planner/cost.py`` and ``kernels/ops.py``.
  * EXECUTION SWEEP: every enumerable plan for representative bit
    configs runs through ``packed_conv2d`` / ``packed_matmul`` and is
    asserted bit-exact against ``ref.conv2d_int_ref`` / the integer
    GEMM oracle — the INT32 lane, the FP32M fp32 word and the wide
    DSP48E2/DSP58 words (two int32 limb planes, ``repro.core.limbs``)
    all through the same kernel bodies.  A future kernel change that
    silently corrupts one datapath fails here by name.
  * NO-X64 SWEEP (``make test-wide-words``): every enumerable
    DSP48E2/DSP58 conv2d / conv1d / matmul plan executes its kernel
    route inside ``jax.enable_x64(False)`` and must match the
    oracle bit-exactly — the tentpole acceptance surface for the
    two-limb representation.  The int64 single-word path survives ONLY
    as the oracle these sweeps compare against.
  * HYPOTHESIS SWEEPS: arbitrary (w_k, w_i) pairs on random datapaths
    through the conv dispatch, and arbitrary u64 operand pairs through
    the limb carry-propagation primitives vs Python mod-2^64 ints.

conftest.py enables ``jax_enable_x64`` for the *oracles*; the kernel
routes themselves never need it (the no-x64 sweep proves it); the
backend is CPU interpret mode.
"""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import planner
from repro.core.datapath import (BSEGPlan, DATAPATHS, INT32, SDVPlan,
                                 plan_bseg)
from repro.kernels import ops, ref

try:
    import hypothesis
    import hypothesis.strategies as st
except ModuleNotFoundError:
    # hypothesis is an optional dev dependency (requirements-dev.txt);
    # the deterministic sweeps below still run.
    class _SkipGiven:
        def given(self, *a, **k):
            return lambda fn: pytest.mark.skip(
                reason="hypothesis not installed")(fn)

        def settings(self, *a, **k):
            return lambda fn: fn

        def assume(self, *a, **k):
            raise RuntimeError("unreachable: test body is skipped")

    class _SkipStrategies:
        def __getattr__(self, name):
            return lambda *a, **k: None

    hypothesis = _SkipGiven()
    st = _SkipStrategies()

RNG = np.random.default_rng(41)

#: datapaths whose conv kernels this repo implements (all of them —
#: the PR-4 acceptance surface).  A conv plan with w_i <= 7 and odd
#: taps on any of these must land on a kernel route, never ref.
CONV_IMPLEMENTED = ("int32", "fp32m", "dsp48e2", "dsp58")
#: datapaths the SDV GEMM/GEMV kernels implement (the kernels are
#: word-generic — int32 words plus the wide DSP48E2/DSP58 words as two
#: int32 limb planes; only FP32M stays ref, because fp32 rounding
#: breaks SDV spill-over tracking, a paper constraint rather than an
#: implementation gap).
MATMUL_KERNEL_DATAPATHS = ("int32", "dsp48e2", "dsp58")

# every (w_bits, a_bits) config the invariant sweep enumerates
BIT_CONFIGS = [(4, 4), (3, 5), (5, 2), (2, 2), (4, 8), (8, 8)]


def _conv_layer(wb, ab, *, h=3, w=5, cin=2, cout=3, k=3):
    return planner.conv2d_spec(f"c{wb}a{ab}", h, w, cin, cout, k, k,
                               w_bits=wb, a_bits=ab)


def _mm_layer(wb, ab):
    return planner.matmul_spec(f"m{wb}a{ab}", 4, 12, 10, w_bits=wb,
                               a_bits=ab, a_signed=False)


def _plan_id(plan):
    d = planner.plan_to_dict(plan)
    return "-".join(f"{k}{v}" for k, v in sorted(d.items()))


# ---------------------------------------------------------------------------
# route invariants: cost model == dispatch, no silent "unimplemented"
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("wb,ab", BIT_CONFIGS)
def test_conv_route_explain_invariants(wb, ab):
    """For every enumerable conv plan: (1) the cost model's route is
    the dispatch route, (2) implemented datapaths never return a
    ref-because-unimplemented reason, (3) ref reasons name a real
    constraint."""
    layer = _conv_layer(wb, ab)
    x_shape = (layer.rows, layer.h, layer.w, layer.c_in)
    w_shape = (layer.c_out, layer.c_in, layer.kh, layer.kw)
    plans = planner.enumerate_plans(layer)
    assert plans, (wb, ab)
    for plan in plans:
        route, reason = planner.route_for(layer, plan)
        cost = planner.score_plan(layer, plan)
        assert cost.route == route and cost.reason == reason, plan
        if isinstance(plan, BSEGPlan):
            disp = ops.select_conv_route(x_shape, w_shape, plan=plan,
                                         explain=True)
            assert disp == (route, reason), plan
            if plan.w_i <= 7:
                # the conv datapath gap is closed: every implemented
                # word lands on a kernel route
                assert plan.spec.name in CONV_IMPLEMENTED
                assert route in ("bseg_conv2d", "bseg_conv1d", "im2col"), \
                    (plan, route, reason)
            else:
                assert route == "ref" and "int8" in reason, (plan, reason)
        else:
            # SDV conv candidates lower to an im2col GEMM; only the
            # int32 word has SDV kernel storage
            if plan.spec.name in MATMUL_KERNEL_DATAPATHS:
                assert route == "im2col", (plan, route, reason)
            else:
                assert route == "ref", (plan, route, reason)


@pytest.mark.parametrize("wb,ab", BIT_CONFIGS)
def test_conv1d_route_explain_invariants(wb, ab):
    layer = planner.conv1d_spec(f"d{wb}a{ab}", 8, 4, w_bits=wb, a_bits=ab,
                                seq=16)
    for plan in planner.enumerate_plans(layer):
        route, reason = planner.route_for(layer, plan)
        cost = planner.score_plan(layer, plan)
        assert cost.route == route and cost.reason == reason, plan
        assert ops.select_conv1d_route(plan, explain=True) == \
            (route, reason), plan
        if plan.w_i <= 7:
            assert route == "bseg_conv1d", (plan, route, reason)
        else:
            assert route == "ref" and "int8" in reason, (plan, reason)


@pytest.mark.parametrize("wb,ab", BIT_CONFIGS)
def test_matmul_route_explain_invariants(wb, ab):
    """The matmul datapath gap is closed: every exact-wrap datapath
    (int32 AND the wide DSP48E2/DSP58 emulation words) lands on an SDV
    kernel route; only FP32M refs, and its reason names the rounding
    constraint — no int32-only storage reason remains."""
    layer = _mm_layer(wb, ab)
    for plan in planner.enumerate_plans(layer):
        route, reason = planner.route_for(layer, plan)
        cost = planner.score_plan(layer, plan)
        assert cost.route == route and cost.reason == reason, plan
        assert ops.select_packed_route(layer.rows, plan=plan,
                                       explain=True) == (route, reason)
        if plan.spec.name in MATMUL_KERNEL_DATAPATHS:
            assert route in ("sdv_matmul", "sdv_matvec"), (plan, route)
        else:
            assert route == "ref", (plan, route)
            assert "fp32" in reason and "int32" not in reason, reason


def test_planner_choice_route_matches_dispatch():
    """The route recorded in every PlanChoice equals what the dispatch
    would do with the chosen plan (UltraNet, all 9 layers)."""
    for c in planner.plan_ultranet(32, first_layer_a_bits=8):
        route, reason = planner.route_for(c.layer, c.plan)
        assert c.cost.route == route and c.cost.reason == reason, c.layer


def test_ultranet_planner_selects_non_int32_datapath():
    """PR-4 acceptance: with the conv gap closed, at least one UltraNet
    layer chooses a non-INT32 datapath plan on a kernel route."""
    choices = planner.plan_ultranet(32, first_layer_a_bits=8)
    wide = [c for c in choices if c.plan.spec.name != "int32"]
    assert wide, [c.plan.spec.name for c in choices]
    for c in wide:
        assert c.cost.route != "ref", (c.layer.name, c.cost.reason)


# ---------------------------------------------------------------------------
# execution sweep: every enumerable plan, bit-exact vs the oracles
# ---------------------------------------------------------------------------

_CONV_EXEC_LAYER = _conv_layer(4, 4)
_CONV_EXEC_PLANS = [p for p in planner.enumerate_plans(_CONV_EXEC_LAYER)
                    if isinstance(p, BSEGPlan)]


#: the row block of the conv2d kernel on the planner's W4A4 plan of the
#: int32, fp32m and dsp48e2 words: 2 rows (the 3-row frame is not a
#: multiple, so the last block is padded) and 3 rows, one output
#: channel per block (bco < C_out)
_CONV_BLOCK_CASES = [(plan_bseg(DATAPATHS[n], 4, 4), bh)
                     for n in ("int32", "fp32m", "dsp48e2") for bh in (2, 3)]


@pytest.mark.parametrize(
    "plan,block_h",
    [(p, None) for p in _CONV_EXEC_PLANS] + _CONV_BLOCK_CASES,
    ids=[_plan_id(p) for p in _CONV_EXEC_PLANS]
    + [f"{_plan_id(p)}-bh{bh}" for p, bh in _CONV_BLOCK_CASES])
def test_conv2d_datapath_diff(plan, block_h):
    """Every enumerable W4A4 BSEG conv plan through ``packed_conv2d``
    (auto route) == the integer conv oracle — both signedness regimes
    (zero point on/off, alternating deterministically per plan, and
    per row block where one is given)."""
    ly = _CONV_EXEC_LAYER
    zp = ((1 << (plan.w_i - 1)) if (plan.lane + plan.n_k + (block_h or 0)) % 2
          else 0)
    rng = np.random.default_rng(zlib.crc32(_plan_id(plan).encode()))
    x = jnp.asarray(rng.integers(-zp, (1 << plan.w_i) - zp,
                                 (1, ly.h, ly.w, ly.c_in)), jnp.int32)
    w = jnp.asarray(rng.integers(-(1 << (plan.w_k - 1)),
                                 1 << (plan.w_k - 1),
                                 (ly.c_out, ly.c_in, ly.kh, ly.kw)),
                    jnp.int8)
    route = ops.select_conv_route(x.shape, w.shape, plan=plan)
    assert route != "ref", plan        # the gap stays closed
    blocks = {} if block_h is None else {"block_h": block_h, "block_co": 1}
    y = ops.packed_conv2d(x, w, plan=plan, mode="auto", zero_point=zp,
                          **blocks)
    want = np.asarray(ref.conv2d_int_ref(x, w))
    assert (np.asarray(y) == want).all(), (plan, route)


@pytest.mark.parametrize("spec_name", CONV_IMPLEMENTED)
def test_conv1d_datapath_diff(spec_name):
    """The causal depthwise conv kernel on each datapath's chosen plans
    (top-k shortlist) == the causal correlation oracle."""
    layer = planner.conv1d_spec("d", 6, 4, w_bits=4, a_bits=4, seq=13)
    choice = planner.choose_plan(
        layer, candidates=planner.enumerate_plans(
            layer, specs=[DATAPATHS[spec_name]]), top_k=3)
    plans = [choice.plan] + [p for p, _ in choice.alternatives]
    taps = jnp.asarray(RNG.integers(-8, 8, (6, 4)))
    xq = jnp.asarray(RNG.integers(-8, 8, (2, 13, 6)), jnp.int8)
    want = np.asarray(ref.conv1d_causal_ref(xq, taps))
    for plan in plans:
        assert ops.select_conv1d_route(plan) == "bseg_conv1d", plan
        kappa, tsum = ops.prepare_bseg_taps(taps, plan)
        y = ops.bseg_conv1d(xq, kappa, tsum, plan=plan, n_taps=4,
                            zero_point=8, use_kernel=True)
        assert (np.asarray(y) == want).all(), plan


_MM_EXEC_LAYERS = [_mm_layer(4, 4),
                   # W4A8: the wide-word payoff config — DSP48E2/DSP58
                   # pack more lanes than INT32 (the 11-bit lane leaves
                   # only 2 on the 32-bit word)
                   _mm_layer(4, 8)]
_MM_EXEC_CASES = [(ly, p) for ly in _MM_EXEC_LAYERS
                  for p in planner.enumerate_plans(ly)]


@pytest.mark.parametrize(
    "ly,plan", _MM_EXEC_CASES,
    ids=[f"w{ly.w_bits}a{ly.a_bits}-{_plan_id(p)}"
         for ly, p in _MM_EXEC_CASES])
def test_matmul_datapath_diff(ly, plan):
    """Every enumerable W4A4/W4A8 SDV plan through ``packed_matmul``
    (auto route: int32 words AND the 2-limb DSP48E2/DSP58 words on the
    kernels; fp32m on the jnp ref decode) == the integer GEMM
    oracle."""
    rng = np.random.default_rng(zlib.crc32(_plan_id(plan).encode()))
    w_int = jnp.asarray(rng.integers(-(1 << (plan.w_a - 1)),
                                     1 << (plan.w_a - 1),
                                     (ly.m, ly.k)))
    lo, hi = ((-(1 << (plan.w_b - 1)), 1 << (plan.w_b - 1))
              if plan.signed_b else (0, 1 << plan.w_b))
    x = jnp.asarray(rng.integers(lo, hi, (ly.rows, ly.k)), jnp.int32)
    route = ops.select_packed_route(ly.rows, plan=plan)
    if plan.spec.name in MATMUL_KERNEL_DATAPATHS:
        # the matmul gap stays closed: exact-wrap words -> kernels
        assert route in ("sdv_matmul", "sdv_matvec"), (plan, route)
    words = ops.prepare_sdv_weights(w_int, plan)
    y = ops.packed_matmul(x, words, plan=plan, m=ly.m)
    want = np.asarray(x) @ np.asarray(w_int).T
    assert (np.asarray(y) == want).all(), (plan, route)


def test_overrun_storage_layout_degrades_to_lossless_ref():
    """A hand-built plan whose packed field + parked sign bits overrun
    the datapath word must (a) route to ref with the overrun reason,
    not raise in auto, and (b) still pack + execute bit-exact — the
    storage widens to two int32 limb planes so the jnp ref decode is
    lossless."""
    bad = SDVPlan(spec=INT32, w_a=4, w_b=8, lane=11, n=4,
                  signed_a=True, signed_b=True)
    assert bad.packed_width + bad.n > 32
    route, reason = ops.select_packed_route(4, plan=bad, explain=True)
    assert route == "ref" and "overruns" in reason
    with pytest.raises(ValueError, match="overruns"):
        ops.select_packed_route(4, plan=bad, mode="sdv_matmul")
    rng = np.random.default_rng(11)
    w_int = jnp.asarray(rng.integers(-8, 8, (10, 6)))
    x = jnp.asarray(rng.integers(-128, 128, (4, 6)), jnp.int32)
    words = ops.prepare_sdv_weights(w_int, bad)
    # widened to limb planes, not truncated — and never int64
    assert words.ndim == 3 and words.shape[0] == 2
    assert words.dtype == jnp.int32
    y = ops.packed_matmul(x, words, plan=bad, m=10)
    assert (np.asarray(y) == np.asarray(x) @ np.asarray(w_int).T).all()


def test_wide_word_matmul_density_beats_int32():
    """The point of closing the matmul corner: at W4A8 the DSP48E2/
    DSP58 words pack more lanes per wide multiply than INT32, and those
    plans now land on a kernel route instead of ref."""
    from repro.core.datapath import DSP48E2, plan_sdv
    wide = plan_sdv(DSP48E2, 4, 8, signed_a=True, signed_b=True,
                    park_sign_bits=True)
    narrow = plan_sdv(INT32, 4, 8, signed_a=True, signed_b=True,
                      park_sign_bits=True)
    assert wide.n > narrow.n, (wide.n, narrow.n)
    route, reason = ops.select_packed_route(4, plan=wide, explain=True)
    assert route in ("sdv_matmul", "sdv_matvec"), (route, reason)


def test_conv2d_full_word_wrapped_bias_plan():
    """Edge of the exact-wrap regime: a hand-dimensioned INT32 plan
    whose biased accumulation word occupies ALL 32 bits (the top lane's
    guard bias lands on the sign bit and wraps).  Mod-2^32 wrap is
    value-preserving under the mask-based extraction, so the kernel
    must stay exact."""
    plan = plan_bseg(INT32, 4, 4, n_k=2, n_i=1, lane=16)
    assert plan.n_lanes * plan.lane == 32
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.integers(0, 16, (1, 4, 7, 2)), jnp.int32)
    w = jnp.asarray(rng.integers(-8, 8, (3, 2, 3, 3)), jnp.int8)
    want = np.asarray(ref.conv2d_int_ref(x, w))
    y = ops.packed_conv2d(x, w, plan=plan, mode="bseg_conv2d",
                          zero_point=0)
    assert (np.asarray(y) == want).all()


def test_plan_bseg_rejects_biased_word_overrun():
    """The dimensioning must refuse guard-swept lanes whose biased
    accumulation word exceeds the accumulator width (the latent
    overflow this harness originally caught: INT32 2x2 with lane 11
    puts the top lane's bias on bit 32) — and the route selectors must
    reject a hand-built plan that bypasses ``plan_bseg``, instead of
    tripping a kernel-internal assert."""
    with pytest.raises(ValueError):
        plan_bseg(INT32, 4, 4, n_k=2, n_i=2, lane=11)
    for plan in planner.enumerate_plans(_CONV_EXEC_LAYER):
        if isinstance(plan, BSEGPlan):
            assert plan.n_lanes * plan.lane <= plan.spec.w_word, plan
    bad = BSEGPlan(spec=INT32, w_k=4, w_i=4, lane=11, n_k=2, n_i=2,
                   w_l=6)
    route, reason = ops.select_conv_route(
        (1, 4, 6, 2), (3, 2, 3, 3), plan=bad, explain=True)
    assert route == "ref" and "accumulator word" in reason
    route, reason = ops.select_conv1d_route(bad, explain=True)
    assert route == "ref" and "accumulator word" in reason
    with pytest.raises(ValueError, match="accumulator word"):
        ops.select_conv_route((1, 4, 6, 2), (3, 2, 3, 3), plan=bad,
                              mode="bseg_conv2d")


def test_conv_sdv_plan_overrides_bit_exact():
    """Planner SDV choices for convs (the im2col override path) on
    every kernel-capable word (int32 + the 2-limb wide words): every
    enumerable override == the conv oracle."""
    ly = _CONV_EXEC_LAYER
    base = plan_bseg(INT32, ly.w_bits, ly.a_bits)
    x = jnp.asarray(RNG.integers(0, 16, (1, ly.h, ly.w, ly.c_in)),
                    jnp.int32)
    w = jnp.asarray(RNG.integers(-8, 8, (ly.c_out, ly.c_in, 3, 3)),
                    jnp.int8)
    want = np.asarray(ref.conv2d_int_ref(x, w))
    overrides = [p for p in planner.enumerate_sdv_plans(
        ly, specs=[DATAPATHS[n] for n in MATMUL_KERNEL_DATAPATHS])]
    assert overrides
    for sdv in overrides:
        y = ops.packed_conv2d(x, w, plan=base, mode="im2col",
                              zero_point=0, sdv_plan=sdv)
        assert (np.asarray(y) == want).all(), sdv


# ---------------------------------------------------------------------------
# no-x64 sweep: every enumerable DSP48E2/DSP58 plan on its kernel route
# inside jax.enable_x64(False) — the tentpole acceptance
# surface for the two-limb int32 representation.  The oracle (`want`)
# is computed in numpy OUTSIDE the context.
# ---------------------------------------------------------------------------

WIDE_SPECS = ("dsp48e2", "dsp58")

_WIDE_MM_CASES = [
    (ly, p) for ly in _MM_EXEC_LAYERS
    for p in planner.enumerate_plans(
        ly, specs=[DATAPATHS[n] for n in WIDE_SPECS])]


@pytest.mark.parametrize(
    "ly,plan", _WIDE_MM_CASES,
    ids=[f"w{ly.w_bits}a{ly.a_bits}-{_plan_id(p)}"
         for ly, p in _WIDE_MM_CASES])
def test_matmul_wide_word_no_x64(ly, plan):
    """Every enumerable wide-word SDV plan dispatches to a Pallas
    kernel route with x64 OFF — storage is two int32 limb planes —
    and matches the integer GEMM oracle bit-exactly."""
    rng = np.random.default_rng(zlib.crc32(_plan_id(plan).encode()))
    w_np = rng.integers(-(1 << (plan.w_a - 1)), 1 << (plan.w_a - 1),
                        (ly.m, ly.k))
    lo, hi = ((-(1 << (plan.w_b - 1)), 1 << (plan.w_b - 1))
              if plan.signed_b else (0, 1 << plan.w_b))
    x_np = rng.integers(lo, hi, (ly.rows, ly.k))
    want = x_np @ w_np.T
    with jax.enable_x64(False):
        route = ops.select_packed_route(ly.rows, plan=plan)
        assert route in ("sdv_matmul", "sdv_matvec"), (plan, route)
        words = ops.prepare_sdv_weights(
            jnp.asarray(w_np, jnp.int32), plan)
        assert words.ndim == 3 and words.shape[0] == 2, plan
        assert words.dtype == jnp.int32, plan
        y = ops.packed_matmul(jnp.asarray(x_np, jnp.int32), words,
                              plan=plan, m=ly.m)
    assert (np.asarray(y) == want).all(), (plan, route)


_WIDE_CONV_PLANS = [
    p for p in planner.enumerate_plans(
        _CONV_EXEC_LAYER, specs=[DATAPATHS[n] for n in WIDE_SPECS])
    if isinstance(p, BSEGPlan)]


@pytest.mark.parametrize(
    "plan", _WIDE_CONV_PLANS,
    ids=[_plan_id(p) for p in _WIDE_CONV_PLANS])
def test_conv2d_wide_word_no_x64(plan):
    """Every enumerable wide-word BSEG conv2d plan on its kernel route
    with x64 OFF == the integer conv oracle."""
    ly = _CONV_EXEC_LAYER
    zp = (1 << (plan.w_i - 1)) if (plan.lane + plan.n_k) % 2 else 0
    rng = np.random.default_rng(zlib.crc32(_plan_id(plan).encode()))
    x_np = rng.integers(-zp, (1 << plan.w_i) - zp,
                        (1, ly.h, ly.w, ly.c_in))
    w_np = rng.integers(-(1 << (plan.w_k - 1)), 1 << (plan.w_k - 1),
                        (ly.c_out, ly.c_in, ly.kh, ly.kw))
    want = np.asarray(ref.conv2d_int_ref(jnp.asarray(x_np),
                                         jnp.asarray(w_np)))
    with jax.enable_x64(False):
        route = ops.select_conv_route(x_np.shape, w_np.shape, plan=plan)
        assert route != "ref", (plan, route)
        y = ops.packed_conv2d(jnp.asarray(x_np, jnp.int32),
                              jnp.asarray(w_np, jnp.int8), plan=plan,
                              mode="auto", zero_point=zp)
    assert (np.asarray(y) == want).all(), (plan, route)


_WIDE_CONV1D_LAYER = planner.conv1d_spec("d", 6, 5, w_bits=4, a_bits=4,
                                         seq=13)
_WIDE_CONV1D_PLANS = [
    p for p in planner.enumerate_plans(
        _WIDE_CONV1D_LAYER, specs=[DATAPATHS[n] for n in WIDE_SPECS])
    if isinstance(p, BSEGPlan)]


@pytest.mark.parametrize(
    "plan", _WIDE_CONV1D_PLANS,
    ids=[_plan_id(p) for p in _WIDE_CONV1D_PLANS])
def test_conv1d_wide_word_no_x64(plan):
    """Every enumerable wide-word BSEG conv1d plan on the depthwise
    kernel with x64 OFF == the causal correlation oracle."""
    rng = np.random.default_rng(zlib.crc32(_plan_id(plan).encode()))
    taps_np = rng.integers(-8, 8, (6, 5))
    x_np = rng.integers(-8, 8, (2, 13, 6))
    want = np.asarray(ref.conv1d_causal_ref(jnp.asarray(x_np),
                                            jnp.asarray(taps_np)))
    with jax.enable_x64(False):
        assert ops.select_conv1d_route(plan) == "bseg_conv1d", plan
        kappa, tsum = ops.prepare_bseg_taps(
            jnp.asarray(taps_np, jnp.int32), plan)
        assert kappa.dtype == jnp.int32 and kappa.shape[0] == 2, plan
        y = ops.bseg_conv1d(jnp.asarray(x_np, jnp.int8), kappa, tsum,
                            plan=plan, n_taps=5, zero_point=8,
                            use_kernel=True)
    assert (np.asarray(y) == want).all(), plan


def test_planner_wide_choice_no_x64():
    """With x64 off the auto planner still picks the wide DSP48E2 n=3
    W4A8 plan (the density win that motivated the limb refactor) and
    prices it as a kernel route."""
    with jax.enable_x64(False):
        choice = planner.choose_plan(
            planner.matmul_spec("m", 4, 256, 512, w_bits=4, a_bits=8))
        assert choice.plan.spec.name in WIDE_SPECS, choice.plan
        assert choice.plan.n == 3, choice.plan
        assert choice.cost.route in ("sdv_matmul", "sdv_matvec"), \
            choice.cost


# ---------------------------------------------------------------------------
# hypothesis: arbitrary u64 operands through the limb primitives
# ---------------------------------------------------------------------------

def _limbs_of(v):
    """Python int (mod 2^64) -> scalar Limbs, no int64 anywhere."""
    from repro.core import limbs as L
    lo, hi = L.const_limbs(v)
    return L.Limbs(jnp.asarray(lo, jnp.int32), jnp.asarray(hi, jnp.int32))


def _int_of(w):
    return (int(np.uint32(np.asarray(w.hi))) << 32) | \
        int(np.uint32(np.asarray(w.lo)))


@hypothesis.given(
    a=st.integers(min_value=0, max_value=2 ** 64 - 1),
    b=st.integers(min_value=0, max_value=2 ** 64 - 1),
    sh=st.integers(min_value=0, max_value=63),
    width=st.integers(min_value=1, max_value=32),
)
@hypothesis.settings(max_examples=50, deadline=None)
def test_limb_carry_property(a, b, sh, width):
    """The limb primitives (add / sub / mul / shifts / mod_pow2 /
    field) == Python mod-2^64 integer arithmetic on arbitrary operand
    pairs, with x64 off — the carry-propagation proof obligation under
    the kernels."""
    from repro.core import limbs as L
    m64 = (1 << 64) - 1
    with jax.enable_x64(False):
        la, lb = _limbs_of(a), _limbs_of(b)
        assert _int_of(L.add(la, lb)) == (a + b) & m64
        assert _int_of(L.sub(la, lb)) == (a - b) & m64
        assert _int_of(L.mul(la, lb)) == (a * b) & m64
        assert _int_of(L.shift_left(la, sh)) == (a << sh) & m64
        assert _int_of(L.shift_right_logical(la, sh)) == a >> sh
        assert _int_of(L.mod_pow2(la, sh + 1)) == a & ((1 << (sh + 1)) - 1)
        lsb = min(sh, 64 - width)
        assert _int_of(L.field(la, lsb, width)) == \
            (a >> lsb) & ((1 << width) - 1)
        # round trip through the transport layout
        assert _int_of(L.from_planes(L.stack_planes(la))) == a


def test_limb_carry_deterministic():
    """Deterministic slice of the limb property (runs even without
    hypothesis): adversarial carry/borrow operand pairs plus a random
    sample, vs Python mod-2^64 ints, x64 off."""
    from repro.core import limbs as L
    m64 = (1 << 64) - 1
    edge = [0, 1, (1 << 31) - 1, 1 << 31, (1 << 32) - 1, 1 << 32,
            (1 << 63) - 1, 1 << 63, m64, 0xDEADBEEFCAFEBABE]
    rng = np.random.default_rng(17)
    rand = [int(v) for v in rng.integers(0, m64, 12, dtype=np.uint64)]
    with jax.enable_x64(False):
        for a in edge + rand[:6]:
            for b in edge[:4] + rand[6:]:
                la, lb = _limbs_of(a), _limbs_of(b)
                assert _int_of(L.add(la, lb)) == (a + b) & m64, (a, b)
                assert _int_of(L.sub(la, lb)) == (a - b) & m64, (a, b)
                assert _int_of(L.mul(la, lb)) == (a * b) & m64, (a, b)
            for sh in (0, 1, 11, 31, 32, 33, 47, 63):
                la = _limbs_of(a)
                assert _int_of(L.shift_left(la, sh)) == (a << sh) & m64
                assert _int_of(L.shift_right_logical(la, sh)) == a >> sh
                assert _int_of(L.field(la, sh, 11)) == (a >> sh) & 0x7FF
            assert _int_of(L.from_planes(L.stack_planes(_limbs_of(a)))) \
                == a


# ---------------------------------------------------------------------------
# hypothesis: arbitrary bitwidth pairs x datapaths through the dispatch
# ---------------------------------------------------------------------------

@hypothesis.given(
    wk=st.integers(min_value=2, max_value=6),
    wi=st.integers(min_value=2, max_value=6),
    spec_name=st.sampled_from(CONV_IMPLEMENTED),
    use_zp=st.booleans(),
    seed=st.integers(min_value=0, max_value=2 ** 31 - 1),
)
@hypothesis.settings(max_examples=20, deadline=None)
def test_conv_datapath_property(wk, wi, spec_name, use_zp, seed):
    """Arbitrary bitwidth pairs on arbitrary datapaths: whatever
    ``plan_bseg`` dimensions must run bit-exact through the dispatch."""
    spec = DATAPATHS[spec_name]
    try:
        plan = plan_bseg(spec, wk, wi)
    except ValueError:
        hypothesis.assume(False)
        return
    hypothesis.assume(plan.w_i <= 7)
    rng = np.random.default_rng(seed)
    h, w = int(rng.integers(1, 5)), int(rng.integers(1, 9))
    cin, cout = int(rng.integers(1, 4)), int(rng.integers(1, 4))
    zp = (1 << (wi - 1)) if use_zp else 0
    x = jnp.asarray(rng.integers(-zp, (1 << wi) - zp, (1, h, w, cin)),
                    jnp.int32)
    wt = jnp.asarray(rng.integers(-(1 << (wk - 1)), 1 << (wk - 1),
                                  (cout, cin, 3, 3)), jnp.int32)
    want = np.asarray(ref.conv2d_int_ref(x, wt))
    y = ops.packed_conv2d(x, wt, plan=plan, mode="bseg_conv2d",
                          zero_point=zp)
    assert (np.asarray(y) == want).all(), plan
