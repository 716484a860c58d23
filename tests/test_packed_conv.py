"""Cross-channel BSEG conv2d (kernels/bseg_conv2d) + the packed_conv2d
dispatch layer: bit-exactness against the integer conv oracle over
shapes, plans and zero points; the dispatch table itself; the 'same'
padding mode of the depthwise kernel; the BSEGConv serving container;
and a hypothesis sweep of BSEG plans through the conv path."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.datapath import DATAPATHS, FP32M, INT32, plan_bseg
from repro.kernels import ops, ref
from repro.kernels.bseg_conv2d import bseg_conv2d_num_multiplies
from repro.models import ultranet as U

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

RNG = np.random.default_rng(23)

PLAN = plan_bseg(INT32, 4, 4)


def _rand_conv(cin, cout, kh, kw, *, w_k=4):
    lim = 1 << (w_k - 1)
    return RNG.integers(-lim, lim, size=(cout, cin, kh, kw))


def _rand_x(b, h, w, c, *, w_i=4, zero_point=0):
    lo, hi = -zero_point, (1 << w_i) - zero_point
    return RNG.integers(lo, hi, size=(b, h, w, c))


def _check(x, w, plan, mode, zero_point=0, **kw):
    xj = jnp.asarray(x, jnp.int32)
    wj = jnp.asarray(w, jnp.int8)
    want = np.asarray(ref.conv2d_int_ref(xj, wj))
    y = ops.packed_conv2d(xj, wj, plan=plan, mode=mode,
                          zero_point=zero_point, **kw)
    assert y.shape == want.shape
    assert (np.asarray(y) == want).all(), (
        mode, plan, np.abs(np.asarray(y) - want).max())


@pytest.mark.parametrize("shape", [
    (2, 8, 9, 3, 16, 3, 3),      # first-layer-like, ragged W
    (1, 6, 6, 8, 12, 3, 3),      # H % bh != 0 fallback
    (1, 5, 7, 4, 6, 5, 5),       # 5x5 taps -> 3 tap groups
    (1, 4, 5, 6, 10, 1, 1),      # pointwise
    (2, 8, 9, 3, 16, 3, 3, 4),   # 4-row blocks, H a multiple
    (1, 7, 6, 4, 16, 3, 3, 3),   # 3-row blocks, the last one padded
])
@pytest.mark.parametrize("mode", ["auto", "bseg_conv2d", "im2col", "ref"])
def test_packed_conv2d_bit_exact(shape, mode):
    b, h, w, cin, cout, kh, kw, *block_h = shape
    x = _rand_x(b, h, w, cin, zero_point=8)
    wt = _rand_conv(cin, cout, kh, kw)
    _check(x, wt, PLAN, mode, zero_point=8, block_co=8,
           block_h=block_h[0] if block_h else None)


@pytest.mark.parametrize("wk,wi", [(2, 2), (2, 4), (3, 3), (4, 4), (5, 2)])
def test_packed_conv2d_plan_sweep(wk, wi):
    """Deterministic plan sweep: bitwidths -> (n_k, n_i, lane, w_l) all
    come out of plan_bseg; the kernel must stay exact for each."""
    plan = plan_bseg(INT32, wk, wi)
    zp = 1 << (wi - 1)
    x = _rand_x(1, 6, 11, 5, w_i=wi, zero_point=zp)
    wt = _rand_conv(5, 7, 3, 3, w_k=wk)
    _check(x, wt, plan, "bseg_conv2d", zero_point=zp)


@pytest.mark.parametrize("block_h", [None, 3])
def test_packed_conv2d_unsigned_inputs_no_zero_point(block_h):
    x = _rand_x(1, 8, 8, 6, zero_point=0)           # already unsigned
    wt = _rand_conv(6, 9, 3, 3)
    _check(x, wt, PLAN, "bseg_conv2d", zero_point=0, block_h=block_h)


def test_packed_conv2d_depthwise_route():
    c = 8
    x = _rand_x(2, 3, 17, c, zero_point=0)
    wt = np.zeros((c, 1, 1, 3), np.int64)
    wt[:, 0, 0, :] = RNG.integers(-8, 8, (c, 3))
    for mode in ("auto", "bseg_conv1d", "ref"):
        _check(x, wt, PLAN, mode, zero_point=0)
    # signed inputs through the zero-point shift
    x2 = _rand_x(1, 2, 9, c, zero_point=8)
    _check(x2, wt, PLAN, "bseg_conv1d", zero_point=8)


def test_bseg_conv1d_same_vs_causal_padding():
    c, n, b, s = 6, 4, 2, 15
    taps = jnp.asarray(RNG.integers(-8, 8, (c, n)))
    xq = jnp.asarray(RNG.integers(-8, 8, (b, s, c)), jnp.int8)
    kappa, tsum = ops.prepare_bseg_taps(taps, PLAN)
    for padding, left in (("causal", n - 1), ("same", (n - 1) // 2)):
        for use_kernel in (True, False):
            y = ops.bseg_conv1d(xq, kappa, tsum, plan=PLAN, n_taps=n,
                                zero_point=8, padding=padding,
                                use_kernel=use_kernel)
            want = ref.conv1d_ref(xq, taps, left)
            assert (np.asarray(y) == np.asarray(want)).all(), \
                (padding, use_kernel)
    with pytest.raises(ValueError):
        ops.bseg_conv1d(xq, kappa, tsum, plan=PLAN, n_taps=n,
                        padding="full")


# ---------------------------------------------------------------------------
# the dispatch table (see kernels/ops.py module docstring)
# ---------------------------------------------------------------------------

def test_conv_dispatch_table_auto():
    sel = ops.select_conv_route
    fp32m = plan_bseg(FP32M, 4, 4)
    dsp = plan_bseg(DATAPATHS["dsp48e2"], 4, 4)
    # (x shape, w shape, plan, backend) -> intended kernel
    assert sel((1, 8, 8, 3), (16, 3, 3, 3), plan=PLAN) == "bseg_conv2d"
    assert sel((1, 8, 8, 64), (36, 64, 1, 1), plan=PLAN) == "im2col"
    assert sel((2, 4, 16, 8), (8, 1, 1, 5), plan=PLAN) == "bseg_conv1d"
    # no pallas backend -> pure-jnp integer conv
    assert sel((1, 8, 8, 3), (16, 3, 3, 3), plan=PLAN,
               use_kernel=False) == "ref"
    # the kernels are word-generic: fp32m (guard bits make fp32 exact)
    # and the int64 emulation words run on the bseg routes
    assert sel((1, 8, 8, 3), (16, 3, 3, 3), plan=fp32m) == "bseg_conv2d"
    assert sel((1, 8, 8, 3), (16, 3, 3, 3), plan=dsp) == "bseg_conv2d"
    assert sel((2, 4, 16, 8), (8, 1, 1, 5), plan=fp32m) == "bseg_conv1d"
    # ... including 1x1, whose SDV-GEMM lowering would need int32 words
    assert sel((1, 8, 8, 64), (36, 64, 1, 1), plan=fp32m) == "bseg_conv2d"
    assert sel((1, 8, 8, 64), (36, 64, 1, 1), plan=dsp) == "bseg_conv2d"
    # even kernels have no stride-1 'same' pad -> ref, depthwise included
    assert sel((1, 8, 8, 3), (16, 3, 2, 2), plan=PLAN) == "ref"
    assert sel((2, 4, 16, 8), (8, 1, 1, 4), plan=PLAN) == "ref"


def test_conv_dispatch_table_explicit_modes():
    sel = ops.select_conv_route
    fp32m = plan_bseg(FP32M, 4, 4)
    assert sel((1, 8, 8, 3), (16, 3, 3, 3), plan=PLAN,
               mode="im2col") == "im2col"
    assert sel((1, 8, 8, 3), (16, 3, 3, 3), plan=PLAN, mode="ref") == "ref"
    # explicit bseg modes accept the non-int32 words now ...
    assert sel((1, 8, 8, 3), (16, 3, 3, 3), plan=fp32m,
               mode="bseg_conv2d") == "bseg_conv2d"
    # ... and im2col runs the wide words too (2-limb SDV storage);
    # only fp32m refuses — rounding breaks SDV spill tracking
    with pytest.raises(ValueError):
        sel((1, 8, 8, 3), (16, 3, 3, 3), plan=fp32m, mode="im2col")
    assert sel((1, 8, 8, 3), (16, 3, 3, 3),
               plan=plan_bseg(DATAPATHS["dsp58"], 4, 4),
               mode="im2col") == "im2col"
    with pytest.raises(ValueError):
        sel((1, 8, 8, 3), (16, 3, 2, 2), plan=PLAN, mode="bseg_conv2d")
    with pytest.raises(ValueError):        # not a depthwise shape
        sel((1, 8, 8, 3), (16, 3, 3, 3), plan=PLAN, mode="bseg_conv1d")
    with pytest.raises(ValueError):        # even taps: no 'same' pad
        sel((2, 4, 16, 8), (8, 1, 1, 4), plan=PLAN, mode="bseg_conv1d")
    with pytest.raises(ValueError):        # channel mismatch
        sel((1, 8, 8, 4), (16, 3, 3, 3), plan=PLAN)
    with pytest.raises(ValueError):
        sel((1, 8, 8, 3), (16, 3, 3, 3), plan=PLAN, mode="bogus")


def test_packed_conv2d_rejects_float_activations():
    x = jnp.ones((1, 4, 4, 3), jnp.float32)
    wt = jnp.asarray(_rand_conv(3, 4, 3, 3), jnp.int8)
    with pytest.raises(ValueError):
        ops.packed_conv2d(x, wt, plan=PLAN)


# ---------------------------------------------------------------------------
# UltraNet wiring: every layer shape, end to end
# ---------------------------------------------------------------------------

def test_ultranet_every_layer_shape_bit_exact():
    """packed_conv2d vs the integer oracle at every conv shape of a
    16x16 UltraNet frame (8 stages + head) — the per-layer version of
    the end-to-end forward test."""
    for s in U.ultranet_layer_shapes(16, 16):
        x = _rand_x(1, s["h"], s["w"], s["cin"], zero_point=0)
        wt = _rand_conv(s["cin"], s["cout"], s["k"], s["k"])
        _check(x, wt, PLAN, "auto", zero_point=0)


def test_ultranet_forward_layerwise_bit_exact():
    """Both paths layer by layer on the SAME per-layer inputs: each
    requantized activation (and the head output) must match exactly."""
    params = U.init_ultranet(0)
    img = jnp.asarray(RNG.integers(0, 16, (1, 16, 16, 3)), jnp.int32)
    plan = plan_bseg(INT32, U.W_BITS, U.A_BITS)
    x = img
    for (cout, k, pool), wt in zip(U.ULTRANET_LAYERS, params.convs):
        acc_ref = U._conv2d_ref(x, wt)
        acc_bseg = U._conv2d_bseg(x, wt, plan)
        assert (np.asarray(acc_ref) == np.asarray(acc_bseg)).all()
        x = U._requant_unsigned(acc_ref)
        if pool:
            b, hh, ww, c = x.shape
            x = x.reshape(b, hh // 2, 2, ww // 2, 2, c).max(axis=(2, 4))
    head_ref = U._conv2d_ref(x, params.head)
    head_bseg = U._conv2d_bseg(x, params.head, plan)
    assert (np.asarray(head_ref) == np.asarray(head_bseg)).all()


def test_ultranet_conv_routes():
    routes = U.ultranet_conv_routes(32, 32)
    assert routes[:-1] == ["bseg_conv2d"] * 8      # all 3x3 stages
    assert routes[-1] == "im2col"                  # 1x1 head is a GEMM


def test_ultranet_forward_rejects_unknown_mode():
    params = U.init_ultranet(0)
    img = jnp.zeros((1, 16, 16, 3), jnp.int32)
    with pytest.raises(ValueError):
        U.ultranet_forward(params, img, mode="bogus")


def test_conv2d_num_multiplies_matches_1d_accounting():
    """The conv2d kernel's multiply count must equal the per-row 1-D
    accounting ultranet_multiplies uses (density unchanged vs seed)."""
    from repro.core import bseg_num_multiplies
    h = w = 16
    for cin, cout, k in ((3, 16, 3), (16, 32, 3)):
        want = h * cout * cin * k \
            * bseg_num_multiplies(k, w + 2 * (k // 2), PLAN)
        got = bseg_conv2d_num_multiplies(h, w, cin, cout, k, k, PLAN)
        assert got == want, (cin, cout, k)


# ---------------------------------------------------------------------------
# the conv2d kernel's row block (ops.bseg_blocks)
# ---------------------------------------------------------------------------

def _blocks_case(h, w, cin, cout, plan, k=3):
    """``bseg_blocks``'s arguments as ``packed_conv2d`` builds them."""
    n_groups = -(-k // plan.n_k)
    n_steps = -(-(w + plan.n_k - 1) // plan.n_i)
    need = (n_steps - 1) * plan.n_i + (n_groups - 1) * plan.n_k + plan.n_i
    w_pad = w + k // 2 + max(k // 2, need - (w + k // 2))
    return h, w_pad, k * cin, min(128, cout), n_groups, plan


def _blocks_cost(bh, h, w_pad, khc, bco, n_groups, plan):
    """(vregs of the word, VMEM bytes of the double-buffered blocks) at
    ``bh`` rows, counted here from the docstring's rule."""
    from repro.kernels import bseg_common
    limbs = bseg_common.word_spec(plan).limbs
    lanes = -(-(bh * bco) // 128) * 128
    vregs = limbs * -(-khc // 8) * lanes // 128
    x_block = w_pad * -(-bh // 32) * 32 * -(-khc // 128) * 128
    kap_block = limbs * n_groups * -(-khc // 8) * 8 * lanes * 4
    return vregs, 2 * (x_block + kap_block + w_pad * lanes * 4)


_BLOCK_PLANS = {n: plan_bseg(DATAPATHS[n], 4, 4)
                for n in ("int32", "fp32m", "dsp48e2", "dsp58")}


@pytest.mark.parametrize("spec_name", sorted(_BLOCK_PLANS))
def test_bseg_blocks_ultranet_stages(spec_name):
    """Every 3x3 stage of the 416 frame: the fewest row blocks whose
    word stays within the vreg target (or within one row's word) and
    whose blocks fit the VMEM budget, H spread evenly over them."""
    plan = _BLOCK_PLANS[spec_name]
    for s in U.ultranet_layer_shapes(416, 416)[:-1]:
        args = _blocks_case(s["h"], s["w"], s["cin"], s["cout"], plan)
        bh = ops.bseg_blocks(*args)
        assert 1 <= bh <= s["h"]
        cap = max(ops.BSEG_TILE_VREGS, _blocks_cost(1, *args)[0])
        fits = [r for r in range(1, s["h"] + 1)
                if _blocks_cost(r, *args)[0] <= cap
                and _blocks_cost(r, *args)[1] <= ops.BSEG_VMEM_BYTES]
        assert bh in fits, (s, bh)
        assert -(-s["h"] // bh) == -(-s["h"] // max(fits)), (s, bh)
        assert bh == -(-s["h"] // -(-s["h"] // bh))       # spread evenly


def test_bseg_blocks_ultranet_choice():
    """The blocks the rule gives UltraNet 416 on the planner's INT32
    W4A4 plan (PERF.md section 3): the thin early stages take the most
    rows, the 64-channel stages the fewest."""
    got = [ops.bseg_blocks(*_blocks_case(s["h"], s["w"], s["cin"],
                                         s["cout"], PLAN))
           for s in U.ultranet_layer_shapes(416, 416)[:-1]]
    assert got == [52, 35, 10, 4, 4, 4, 4, 4]


@pytest.mark.parametrize("spec_name", sorted(_BLOCK_PLANS))
@pytest.mark.parametrize("h,w,cin,cout", [
    (1, 1, 1, 1), (1, 9, 3, 16), (2, 3, 2, 5), (3, 5, 64, 64), (5, 2, 6, 7),
])
def test_bseg_blocks_tiny_frames(spec_name, h, w, cin, cout):
    bh = ops.bseg_blocks(*_blocks_case(h, w, cin, cout,
                                       _BLOCK_PLANS[spec_name]))
    assert 1 <= bh <= h


# ---------------------------------------------------------------------------
# BSEGConv serving container
# ---------------------------------------------------------------------------

def test_bseg_conv_serving_container():
    from repro.models.quantized import (default_bseg_plan, pack_conv_bseg)
    from repro.models.ssm import short_conv_apply
    C, taps = 24, 4
    params = {
        "w": jnp.asarray(RNG.standard_normal((C, taps)) * 0.5, jnp.float32),
        "b": jnp.asarray(RNG.standard_normal(C) * 0.1, jnp.float32),
    }
    x = jnp.asarray(RNG.standard_normal((2, 16, C)), jnp.float32)
    y_f, st_f = short_conv_apply(params, x)
    qc = pack_conv_bseg(params, default_bseg_plan(4))
    y_q, st_q = short_conv_apply(qc, x)       # container dispatch
    assert y_q.shape == y_f.shape and st_q.shape == st_f.shape
    err = np.abs(np.asarray(y_q) - np.asarray(y_f)).max() \
        / np.abs(np.asarray(y_f)).max()
    assert err < 0.3, err                      # W4A4 dynamic quant
    # the state is the raw float history, unchanged by quantization
    assert np.allclose(np.asarray(st_q), np.asarray(st_f))


def test_bseg_conv_stacked_layer_packing():
    """Stacked [L, C, taps] conv params (scanned blocks): packing the
    stack then slicing layer l must equal packing layer l alone."""
    import jax
    from repro.models.quantized import (BSEGConv, default_bseg_plan,
                                        pack_conv_bseg)
    L, C, taps = 3, 8, 4
    w = jnp.asarray(RNG.standard_normal((L, C, taps)), jnp.float32)
    b = jnp.asarray(RNG.standard_normal((L, C)), jnp.float32)
    stacked = pack_conv_bseg({"w": w, "b": b}, default_bseg_plan(4))
    for layer in range(L):
        single = pack_conv_bseg({"w": w[layer], "b": b[layer]},
                                default_bseg_plan(4))
        sliced = jax.tree_util.tree_map(lambda a: a[layer], stacked)
        assert isinstance(sliced, BSEGConv)
        for f in ("kappa", "tap_sum", "scale", "bias"):
            assert (np.asarray(getattr(sliced, f))
                    == np.asarray(getattr(single, f))).all(), (layer, f)


def test_serve_params_packs_short_convs():
    from repro.models.quantized import BSEGConv, serve_params
    params = {
        "blocks": {"ssm": {"conv": {
            "w": jnp.ones((2, 32, 4), jnp.float32),
            "b": jnp.zeros((2, 32), jnp.float32)}}},
        "lm_head": jnp.ones((64, 128), jnp.float32),
    }
    qp = serve_params(params, bits=4, min_size=1, compute="sdv")
    assert isinstance(qp["blocks"]["ssm"]["conv"], BSEGConv)
    # memory mode / conv_bseg=False keep the float conv container
    qp2 = serve_params(params, bits=4, min_size=1, compute="memory")
    assert isinstance(qp2["blocks"]["ssm"]["conv"], dict)
    qp3 = serve_params(params, bits=4, min_size=1, compute="sdv",
                       conv_bseg=False)
    assert isinstance(qp3["blocks"]["ssm"]["conv"], dict)


# ---------------------------------------------------------------------------
# hypothesis sweep: plans x tap counts x zero points through the kernel
# ---------------------------------------------------------------------------

@hypothesis.given(
    wk=st.integers(min_value=2, max_value=5),
    wi=st.integers(min_value=2, max_value=5),
    kh=st.sampled_from([1, 3]),
    kw=st.sampled_from([1, 3, 5]),
    cin=st.integers(min_value=1, max_value=6),
    cout=st.integers(min_value=1, max_value=6),
    use_zp=st.booleans(),
    seed=st.integers(min_value=0, max_value=2 ** 31 - 1),
)
@hypothesis.settings(max_examples=25, deadline=None)
def test_packed_conv2d_property(wk, wi, kh, kw, cin, cout, use_zp, seed):
    plan = plan_bseg(INT32, wk, wi)
    zp = (1 << (wi - 1)) if use_zp else 0
    rng = np.random.default_rng(seed)
    h, w = int(rng.integers(1, 7)), int(rng.integers(1, 12))
    lim = 1 << (wk - 1)
    x = rng.integers(-zp, (1 << wi) - zp, size=(1, h, w, cin))
    wt = rng.integers(-lim, lim, size=(cout, cin, kh, kw))
    xj, wj = jnp.asarray(x, jnp.int32), jnp.asarray(wt, jnp.int32)
    want = np.asarray(ref.conv2d_int_ref(xj, wj))
    y = ops.packed_conv2d(xj, wj, plan=plan, mode="bseg_conv2d",
                          zero_point=zp)
    assert (np.asarray(y) == want).all()
