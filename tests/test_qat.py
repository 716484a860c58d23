"""Packed QAT subsystem tests (DESIGN.md §6).

The contract under test: training sees EXACTLY the integers serving
decodes.  Concretely —

  * THREE-PATH IDENTITY: the QAT fake-quant (``train/qat/ste``), the
    serving weight prep (``models/quantized``) and the raw shared rule
    (``quant/quantizer``) produce bit-identical (q, scale) for the same
    kernel — one function, three consumers.
  * PACKED == DECODE SWEEP: for every enumerable plan at W4A4/W4A8 on
    all four datapaths, the ``custom_vjp`` packed STE forward
    (``packed_matmul`` / ``packed_conv2d`` dispatch) equals the
    fake-quant integer-decode forward bitwise — the packed routes
    return the exact correlation, so the dequantized floats match to
    the last ulp (test_datapath_diff's exec-sweep style).
  * STE GRADIENTS: the custom backward equals autodiff through the
    straight-through surrogate (quantizers as identity).
  * WRAP / TRAIN / EXPORT: ``qat_params`` wraps exactly the layer set
    ``serve_params`` packs; a train step moves the float masters; the
    export round-trips through the serving rewrite with matching eval.
  * PLAN-CACHE HANDOFF: ``bitsearch`` warms a cache file that
    ``plan_policy="cache"`` consumers resolve from without re-planning
    (file bytes unchanged).
  * PACKED GRAD ALL-REDUCE: SDV word packing in ``grad_compress`` is
    bit-exact vs the unpacked int8 reduce, pads odd sizes, survives the
    device bound, and refuses past it.
  * NO-X64: the whole training path — STE packed forward on a wide
    datapath, Q8 optimizer moments, grad word packing — runs inside
    ``jax.enable_x64(False)`` unchanged.
"""
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import planner
from repro.core.datapath import BSEGPlan
from repro.quant import quantizer
from repro.train import grad_compress, optimizer
from repro.train.qat import bitsearch, ste

try:
    import hypothesis
    import hypothesis.strategies as st
except ModuleNotFoundError:
    # optional dev dependency; the deterministic sweeps still run
    class _SkipGiven:
        def given(self, *a, **k):
            return lambda fn: pytest.mark.skip(
                reason="hypothesis not installed")(fn)

        def settings(self, *a, **k):
            return lambda fn: fn

    class _SkipStrategies:
        def __getattr__(self, name):
            return lambda *a, **k: None

    hypothesis = _SkipGiven()
    st = _SkipStrategies()

RNG = np.random.default_rng(7)


def _plan_id(plan):
    d = planner.plan_to_dict(plan)
    return "-".join(f"{k}{v}" for k, v in sorted(d.items()))


def _bits_equal(a, b):
    a, b = np.asarray(a), np.asarray(b)
    return a.shape == b.shape and \
        np.array_equal(a.view(np.uint32), b.view(np.uint32))


# ---------------------------------------------------------------------------
# three-path quantization identity (the shared rule)
# ---------------------------------------------------------------------------

def test_three_path_quantization_identity():
    """QAT fake-quant, serving weight prep and the raw quantizer rule
    pin bit-identical (q, scale) — regression against any one path
    growing its own epsilon/clip/round variant."""
    from repro.models.quantized import pack_linear, pack_linear_sdv
    kernel = jnp.asarray(RNG.standard_normal((24, 16)), jnp.float32)
    bits = 4

    # path 1: the rule itself
    amax = jnp.max(jnp.abs(kernel), axis=0)
    scale0 = quantizer.symmetric_scale(amax, bits)
    q0 = quantizer.symmetric_qvalues(kernel, scale0, bits)

    # path 2: QAT
    q1, scale1 = ste.quantize_weights(kernel, bits)
    assert _bits_equal(scale0, scale1)
    assert np.array_equal(np.asarray(q0), np.asarray(q1))

    # path 3a: serving SDV container (same scale; words are the packed
    # image of the same q)
    from repro.kernels import ops
    plan = planner.choose_plan(
        planner.matmul_spec("t", 4, 24, 16, w_bits=bits, a_bits=8)).plan
    sdv = pack_linear_sdv(kernel, plan)
    assert _bits_equal(scale0, sdv.scale)
    want_words = ops.prepare_sdv_weights(
        jnp.asarray(q0, jnp.int32).T, plan)
    assert np.array_equal(np.asarray(sdv.words), np.asarray(want_words))

    # path 3b: serving memory container (amax over the same axis)
    pk = pack_linear(kernel, bits)
    assert _bits_equal(scale0, pk.scale[0])

    # the activation rule too: QAT act quantization == the quantizer
    x = jnp.asarray(RNG.standard_normal((3, 24)), jnp.float32)
    xq, xs = ste.quantize_acts(x, 8)
    xs0 = quantizer.symmetric_scale(
        jnp.max(jnp.abs(x), axis=-1, keepdims=True), 8)
    assert _bits_equal(xs, xs0)
    assert np.array_equal(
        np.asarray(xq),
        np.asarray(quantizer.symmetric_qvalues(x, xs0, 8), np.int32))


# ---------------------------------------------------------------------------
# packed forward == integer-decode forward, every enumerable plan
# ---------------------------------------------------------------------------

_MM_LAYERS = [planner.matmul_spec(f"m4a{ab}", 3, 24, 10, w_bits=4,
                                  a_bits=ab) for ab in (4, 8)]
_MM_CASES = [(ly, p) for ly in _MM_LAYERS
             for p in planner.enumerate_plans(ly)]


@pytest.mark.parametrize(
    "ly,plan", _MM_CASES,
    ids=[f"w{ly.w_bits}a{ly.a_bits}-{_plan_id(p)}" for ly, p in _MM_CASES])
def test_ste_dense_packed_equals_decode(ly, plan):
    """``ste_dense`` with a plan (packed dispatch on the plan's
    datapath) == ``ste_dense`` without one (plain integer decode),
    bitwise, for every enumerable W4A4/W4A8 plan — all four datapaths
    enumerate here (int32 / fp32m / dsp48e2 / dsp58)."""
    rng = np.random.default_rng(zlib.crc32(_plan_id(plan).encode()))
    x = jnp.asarray(rng.standard_normal((ly.rows, ly.k)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((ly.k, ly.m)), jnp.float32)
    y_packed = ste_dense_call(x, k, ly.w_bits, ly.a_bits, plan)
    y_decode = ste_dense_call(x, k, ly.w_bits, ly.a_bits, None)
    assert _bits_equal(y_packed, y_decode), (plan, )


def ste_dense_call(x, k, wb, ab, plan):
    return ste.ste_dense(x, k, wb, ab, plan, False)


_CONV_LAYER = planner.conv2d_spec("c4a4", 3, 5, 2, 3, 3, 3, w_bits=4,
                                  a_bits=4)
_CONV_PLANS = [p for p in planner.enumerate_plans(_CONV_LAYER)
               if isinstance(p, BSEGPlan)]


@pytest.mark.parametrize("plan", _CONV_PLANS,
                         ids=[_plan_id(p) for p in _CONV_PLANS])
def test_ste_conv2d_packed_equals_decode(plan):
    """``ste_conv2d`` packed (BSEG dispatch) == integer-decode
    reference, bitwise, for every enumerable W4A4 conv plan."""
    ly = _CONV_LAYER
    rng = np.random.default_rng(zlib.crc32(_plan_id(plan).encode()))
    x = jnp.asarray(rng.standard_normal((2, ly.h, ly.w, ly.c_in)),
                    jnp.float32)
    w = jnp.asarray(rng.standard_normal((ly.c_out, ly.c_in, ly.kh,
                                         ly.kw)), jnp.float32)
    y_packed = ste.ste_conv2d(x, w, 4, 4, plan, False)
    y_decode = ste.ste_conv2d(x, w, 4, 4, None, False)
    assert _bits_equal(y_packed, y_decode), plan


@hypothesis.settings(max_examples=25, deadline=None)
@hypothesis.given(st.integers(0, 10**9), st.integers(1, 6),
                  st.integers(0, len(_MM_CASES) - 1))
def test_ste_dense_packed_equals_decode_hypothesis(seed, rows, case):
    """Random data / row counts over random enumerable plans — the
    deterministic sweep's fuzzed twin."""
    ly, plan = _MM_CASES[case]
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((rows, ly.k)) * 3, jnp.float32)
    k = jnp.asarray(rng.standard_normal((ly.k, ly.m)), jnp.float32)
    y_packed = ste.ste_dense(x, k, ly.w_bits, ly.a_bits, plan, False)
    y_decode = ste.ste_dense(x, k, ly.w_bits, ly.a_bits, None, False)
    assert _bits_equal(y_packed, y_decode)


# ---------------------------------------------------------------------------
# STE gradients == straight-through surrogate autodiff
# ---------------------------------------------------------------------------

def _st(x, fq):
    """Straight-through: value of fq, gradient of the identity."""
    return x + jax.lax.stop_gradient(fq - x)


def test_ste_dense_gradients():
    x = jnp.asarray(RNG.standard_normal((5, 24)), jnp.float32)
    k = jnp.asarray(RNG.standard_normal((24, 10)), jnp.float32)
    g = jnp.asarray(RNG.standard_normal((5, 10)), jnp.float32)

    def loss(x_, k_):
        return jnp.sum(ste.ste_dense(x_, k_, 4, 8, None, False) * g)

    def surrogate(x_, k_):
        xq, xs = ste.quantize_acts(x_, 8)
        qw, sw = ste.quantize_weights(k_, 4)
        x_fq = _st(x_, xq.astype(jnp.float32) * xs)
        w_fq = _st(k_, qw.astype(jnp.float32) * sw[None, :])
        return jnp.sum((x_fq @ w_fq) * g)

    gx, gk = jax.grad(loss, argnums=(0, 1))(x, k)
    sx, sk = jax.grad(surrogate, argnums=(0, 1))(x, k)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(sx), atol=1e-4)
    np.testing.assert_allclose(np.asarray(gk), np.asarray(sk), atol=1e-4)


def test_ste_conv2d_gradients():
    x = jnp.asarray(RNG.standard_normal((2, 4, 5, 3)), jnp.float32)
    w = jnp.asarray(RNG.standard_normal((4, 3, 3, 3)), jnp.float32)
    g = jnp.asarray(RNG.standard_normal((2, 4, 5, 4)), jnp.float32)

    def loss(x_, w_):
        return jnp.sum(ste.ste_conv2d(x_, w_, 4, 4, None, False) * g)

    def surrogate(x_, w_):
        wf = w_.astype(jnp.float32)
        amax = jnp.max(jnp.abs(wf), axis=(1, 2, 3), keepdims=True)
        sw = quantizer.symmetric_scale(amax, 4)
        qw = quantizer.symmetric_qvalues(wf, sw, 4)
        lo, hi = jnp.min(x_), jnp.max(x_)
        xs = quantizer.asymmetric_scale(lo, hi, 4)
        xq_u = quantizer.asymmetric_qvalues(x_, lo, xs, 4)
        x_fq = _st(x_, lo + xs * xq_u)
        w_fq = _st(w_, qw * sw)
        return jnp.sum(ste._conv_float(x_fq, w_fq) * g)

    gx, gw = jax.grad(loss, argnums=(0, 1))(x, w)
    sx, sw_ = jax.grad(surrogate, argnums=(0, 1))(x, w)
    np.testing.assert_allclose(np.asarray(gx), np.asarray(sx), atol=1e-4)
    np.testing.assert_allclose(np.asarray(gw), np.asarray(sw_), atol=1e-4)


# ---------------------------------------------------------------------------
# wrap / train / export round-trip on a registry arch
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def qat_run():
    from repro.train import qat
    from repro.train.qat.loop import QATRunConfig, run_qat
    qcfg = QATRunConfig(steps=2, global_batch=2, seq=32,
                        min_size=1 << 10, packed_forward=False,
                        eval_batches=1, lr=1e-3)
    return qcfg, run_qat(qcfg, log=lambda *_: None)


def test_qat_wraps_exactly_the_serving_layer_set(qat_run):
    """``qat_params`` and ``serve_params`` pack the same layers — the
    walk rules cannot drift apart silently."""
    from repro.models import serve_params
    from repro.models.quantized import SDVLinear
    qcfg, res = qat_run
    served = serve_params(ste.float_params(res["params"]), bits=4,
                          min_size=qcfg.min_size, compute="sdv",
                          act_bits=8)

    def count(t, pred):
        if pred(t):
            return 1
        if isinstance(t, dict):
            return sum(count(v, pred) for v in t.values())
        return 0

    n_sdv = count(served, lambda t: isinstance(t, SDVLinear))
    assert res["qat_layers"] == n_sdv > 0


def test_qat_trains_and_matches_float_eval(qat_run):
    """QAT from float init: losses finite, masters move, eval within
    tolerance of the float-init baseline."""
    qcfg, res = qat_run
    assert len(res["losses"]) == qcfg.steps
    assert all(np.isfinite(l) for l in res["losses"])
    assert np.isfinite(res["qat_eval"])
    # two steps of QAT must stay near the float baseline (same init)
    assert abs(res["qat_eval"] - res["float_eval_at_init"]) < 0.5
    # step times recorded by the monitor (honest timing path)
    assert len(res["step_times"]) == qcfg.steps


def test_qat_export_serves(qat_run):
    """Exported params run the serving forward with matching eval —
    the QAT -> export -> serve contract."""
    from repro.train.qat.loop import evaluate, export_for_serving
    qcfg, res = qat_run
    served = export_for_serving(qcfg, res["params"], plan_policy="auto")
    served_eval = evaluate(res["cfg"], served, res["data"],
                           batches=1, offset=qcfg.eval_offset)
    assert abs(served_eval - res["qat_eval"]) < 0.1, \
        (served_eval, res["qat_eval"])


def test_qat_packed_forward_bit_matches_decode_forward():
    """One jitted train-loss on a wrapped tree: packed-plan forward ==
    plan-free decode forward bitwise (the plan only changes the
    route, never the arithmetic)."""
    k = jnp.asarray(RNG.standard_normal((64, 1024)), jnp.float32)
    x = jnp.asarray(RNG.standard_normal((4, 64)), jnp.float32)
    plan = planner.choose_plan(
        planner.matmul_spec("t", 4, 64, 1024, w_bits=4, a_bits=8)).plan
    packed = ste.QATLinear(kernel=k, w_bits=4, a_bits=8, plan=plan)
    decode = ste.QATLinear(kernel=k, w_bits=4, a_bits=8, plan=None)
    y_p = jax.jit(lambda c: c.qat_apply(x))(packed)
    y_d = jax.jit(lambda c: c.qat_apply(x))(decode)
    assert _bits_equal(y_p, y_d)


# ---------------------------------------------------------------------------
# bitsearch -> warm plan cache -> cache-policy consumers never re-plan
# ---------------------------------------------------------------------------

def test_bitsearch_warm_cache_serves_without_replanning(tmp_path):
    from repro.models import serve_params
    cache = str(tmp_path / "plans.json")
    params = {"layer": {"kernel": jnp.asarray(
        RNG.standard_normal((64, 1024)), jnp.float32)}}
    precision, report = bitsearch.search_bitwidths(
        params, candidates=((4, 8),), rows_list=(1, 8),
        cache_path=cache)
    assert precision == {"layer/kernel": (4, 8)}
    assert report[0].route != "ref"
    before = open(cache).read()
    assert "bitsearch" in before
    serve_params(params, bits=4, act_bits=8, compute="sdv",
                 plan_policy="cache", plan_cache=cache, rows=8)
    assert open(cache).read() == before       # pure cache hits
    wrapped = ste.qat_params(params, w_bits=4, a_bits=8,
                             plan_policy="cache", plan_cache=cache,
                             rows=8, use_kernel=False)
    assert wrapped["layer"]["kernel"].plan is not None
    assert open(cache).read() == before


def test_bitsearch_sensitivity_orders_bitwidths():
    """More bits -> strictly lower quantization MSE proxy."""
    k = jnp.asarray(RNG.standard_normal((128, 64)), jnp.float32)
    s4 = bitsearch.sensitivity_proxy(k, 4)
    s8 = bitsearch.sensitivity_proxy(k, 8)
    assert 0 < s8 < s4 < 1


# ---------------------------------------------------------------------------
# SDV-packed gradient all-reduce: bit-exact vs unpacked
# ---------------------------------------------------------------------------

def test_grad_words_roundtrip_matches_int32_sum():
    """Numpy-emulated multi-device reduce through the real pack/decode:
    summed words decode to the exact int32 lane sums (odd size pads)."""
    rng = np.random.default_rng(0)
    n_dev, size = 4, 1001
    q_dev = rng.integers(-127, 128, (n_dev, size)).astype(np.int8)
    words = jnp.stack([grad_compress.pack_grad_words(jnp.asarray(q))
                       for q in q_dev])
    dec = grad_compress.unpack_grad_words(
        jnp.sum(words.astype(jnp.int32), axis=0), size)
    assert np.array_equal(np.asarray(dec),
                          q_dev.astype(np.int32).sum(axis=0))


def test_grad_words_survive_device_bound():
    """Worst-case +/-127 lanes at MAX_PACKED_DEVICES decode exactly."""
    nd = grad_compress.MAX_PACKED_DEVICES
    for v in (127, -127):
        q = jnp.full((64,), v, jnp.int8)
        w = grad_compress.pack_grad_words(q) * nd
        dec = grad_compress.unpack_grad_words(w, 64)
        assert np.array_equal(np.asarray(dec), np.full(64, v * nd))


def test_compressed_allreduce_packed_bit_exact():
    """End-to-end shard_map reduce: packed words == unpacked int8 path
    bitwise (result AND error-feedback state)."""
    from jax.sharding import Mesh
    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    rng = np.random.default_rng(3)
    g = {"w": jnp.asarray(rng.standard_normal((1, 4097)), jnp.float32)}
    e = {"w": jnp.zeros_like(g["w"])}
    gh_p, e_p = grad_compress.compressed_allreduce(
        g, e, mesh, pack_words=True)
    gh_u, e_u = grad_compress.compressed_allreduce(
        g, e, mesh, pack_words=False)
    assert _bits_equal(gh_p["w"], gh_u["w"])
    assert _bits_equal(e_p["w"], e_u["w"])


def test_compressed_allreduce_guards_device_bound():
    class FakeMesh:
        shape = {"data": grad_compress.MAX_PACKED_DEVICES + 1}

    with pytest.raises(ValueError, match="overflow"):
        grad_compress.compressed_allreduce({}, {}, FakeMesh(),
                                           pack_words=True)


# ---------------------------------------------------------------------------
# no-x64 audit: the training path is int32/float32 clean
# ---------------------------------------------------------------------------

def test_training_path_runs_without_x64():
    """STE packed forward on a wide datapath, Q8 moments, grad word
    packing — all inside ``jax.enable_x64(False)`` (conftest enables x64 for the
    oracles; the training path must never need it)."""
    with jax.enable_x64(False):
        # STE forward on a wide (two-limb) datapath plan
        ly = planner.matmul_spec("t", 2, 24, 10, w_bits=4, a_bits=8)
        from repro.core.datapath import DATAPATHS
        plans = planner.enumerate_plans(ly, specs=[DATAPATHS["dsp48e2"]])
        x = jnp.asarray(RNG.standard_normal((2, 24)), jnp.float32)
        k = jnp.asarray(RNG.standard_normal((24, 10)), jnp.float32)
        y_p = ste.ste_dense(x, k, 4, 8, plans[0], False)
        y_d = ste.ste_dense(x, k, 4, 8, None, False)
        assert _bits_equal(y_p, y_d)

        # optimizer: Q8 moment roundtrip (incl. the saturation clip)
        m = jnp.asarray(RNG.standard_normal((4, 33)), jnp.float32) * 1e-3
        q8 = optimizer._q8(m)
        assert q8.q.dtype == jnp.int8
        assert int(jnp.max(q8.q)) <= 127 and int(jnp.min(q8.q)) >= -127
        back = optimizer._dq8(q8)
        assert float(jnp.max(jnp.abs(back - m))) <= \
            float(jnp.max(q8.scale)) * 0.51

        # one full AdamW update with 8-bit moments
        ocfg = optimizer.OptConfig(lr=1e-3, warmup=1, total_steps=4,
                                   moments_8bit=True)
        p = {"w": jnp.asarray(RNG.standard_normal((8, 33)), jnp.float32)}
        opt = optimizer.init(ocfg, p)
        grads = {"w": jnp.asarray(RNG.standard_normal((8, 33)),
                                  jnp.float32)}
        p2, opt2, metrics = optimizer.update(ocfg, grads, opt, p)
        assert np.isfinite(float(metrics["grad_norm"]))
        assert not np.array_equal(np.asarray(p2["w"]), np.asarray(p["w"]))

        # grad word packing stays int32
        q = jnp.asarray(RNG.integers(-127, 128, 65), jnp.int8)
        w = grad_compress.pack_grad_words(q)
        assert w.dtype == jnp.int32
        assert np.array_equal(
            np.asarray(grad_compress.unpack_grad_words(w, 65)),
            np.asarray(q, np.int32))


def test_run_training_sync_inside_timed_region():
    """The injectable clock/sync seam: run_training must call ``sync``
    INSIDE the monitor's timed region, so async dispatch cannot fake
    fast steps (the seed-era loop timed only dispatch)."""
    from repro.train import loop, straggler

    t = {"v": 0.0}

    def clock():
        return t["v"]

    def sync(_):
        t["v"] += 1.0          # device work "completes" during sync

    def step_fn(p, o, b):
        return p, o, {"loss": jnp.zeros(())}

    class Data:
        def batch_at(self, s):
            return {"tokens": np.zeros((1, 2), np.int32)}

    mon = straggler.StepMonitor(clock=clock)
    seen = []
    loop.run_training(None, None, {}, {}, Data(), steps=3,
                      monitor=mon, clock=clock, sync=sync,
                      step_fn=step_fn,
                      on_step=lambda s, p, o, m, dt, mo:
                      seen.append(dt))
    assert seen == [1.0, 1.0, 1.0]     # sync's second is inside dt
