"""Mixed-precision packing planner: every enumerated plan satisfies
Eqs. 4/7-10 against core/datapath.py (hypothesis property sweep +
deterministic checks), unsatisfiable (bits, datapath) combos enumerate
empty, the cost model penalizes ref fallbacks, planner-chosen plans
are bit-exact vs the ref oracles on UltraNet layer shapes and through
``serve_params(plan_policy="auto")``, the autotune JSON cache round
trips, and the ``python -m repro.planner`` CLI runs."""
import json

import jax.numpy as jnp
import numpy as np
import pytest

from repro import planner
from repro.core.datapath import (BSEGPlan, DATAPATHS, FP32M, INT32, SDVPlan,
                                 plan_bseg, plan_sdv, sdv_lane_size)
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

    class _SkipStrategies:
        def __getattr__(self, name):
            return lambda *a, **k: None

    hypothesis = _SkipGiven()
    st = _SkipStrategies()

RNG = np.random.default_rng(31)


# ---------------------------------------------------------------------------
# Eq. 4 / 7-10 validity of every enumerated plan
# ---------------------------------------------------------------------------

def _check_sdv(plan: SDVPlan):
    """Eq. 4 + the port/word budgets of core/datapath.plan_sdv."""
    assert plan.lane >= max(2, sdv_lane_size(plan.w_a, plan.w_b)), plan
    assert plan.n >= 1
    budget = plan.spec.packed_port_budget(plan.w_b)
    assert plan.packed_width <= budget, plan
    if plan.signed_a:    # parked sign bits must fit the storage word
        assert plan.packed_width + plan.n <= plan.spec.w_word, plan


def _check_bseg(plan: BSEGPlan):
    """Eqs. 7, 8 (ports), the word budget, and Eqs. 9, 10 (guards)."""
    wa = (plan.n_k - 1) * plan.lane + plan.w_k + 1
    wb = (plan.n_i - 1) * plan.lane + plan.w_i + 1
    assert wa <= plan.spec.w_packed, plan                       # Eq. 7
    assert wb <= plan.spec.w_other, plan                        # Eq. 8
    assert wa + wb <= plan.spec.w_word, plan
    m = min(plan.n_k, plan.n_i)
    bias = 1 << (plan.lane - 1)
    assert bias >= m * (1 << (plan.w_k - 1)) * ((1 << plan.w_i) - 1), \
        plan                                                    # Eq. 9
    assert bias > m * ((1 << (plan.w_k - 1)) - 1) \
        * ((1 << plan.w_i) - 1) + ((1 << plan.w_l) - 1), plan   # Eq. 10


def _sdv_feasible(spec, layer):
    for w_b, signed_b in ((layer.a_bits, True),) if layer.a_signed else \
            ((layer.a_bits, False), (layer.a_bits + 1, True)):
        try:
            plan_sdv(spec, layer.w_bits, w_b, signed_a=True,
                     signed_b=signed_b, park_sign_bits=True)
            return True
        except ValueError:
            pass
    return False


@hypothesis.given(w=st.integers(min_value=1, max_value=12),
                  a=st.integers(min_value=1, max_value=12),
                  a_signed=st.booleans())
@hypothesis.settings(max_examples=60, deadline=None)
def test_enumerated_plans_satisfy_dimensioning(w, a, a_signed):
    layer = planner.matmul_spec("p", 8, 64, 32, w_bits=w, a_bits=a,
                                a_signed=a_signed)
    conv = planner.conv2d_spec("c", 8, 8, 4, 4, 3, 3, w_bits=w, a_bits=a)
    for spec in DATAPATHS.values():
        sdv = planner.enumerate_sdv_plans(layer, specs=[spec])
        for p in sdv:
            _check_sdv(p)
        # empty iff the Eq. 4 solver itself finds the combo infeasible
        assert bool(sdv) == _sdv_feasible(spec, layer), (spec.name, w, a)
        bseg = planner.enumerate_bseg_plans(conv, specs=[spec])
        for p in bseg:
            _check_bseg(p)
        try:
            plan_bseg(spec, w, a)
            feasible = True
        except ValueError:
            feasible = False
        assert bool(bseg) == feasible, (spec.name, w, a)


def test_enumeration_deterministic_cases():
    conv = planner.conv2d_spec("c", 16, 16, 8, 8, 3, 3, w_bits=4, a_bits=4)
    bseg = planner.enumerate_bseg_plans(conv, specs=[INT32])
    for p in bseg:
        _check_bseg(p)
    # the uniform default plan (n_k=2 x n_i=2) is among the candidates
    assert any(p.n_k == 2 and p.n_i == 2 for p in bseg)
    # guard-bit sweep: lane sizes above the Eq. 9 minimum are explored
    lanes = {(p.n_k, p.n_i, p.lane) for p in bseg}
    assert (2, 2, 9) in lanes and (2, 2, 10) in lanes
    # unsatisfiable: 12-bit weights on the fp32m 24-bit word
    wide = planner.conv2d_spec("c", 8, 8, 4, 4, 3, 3, w_bits=12, a_bits=12)
    assert planner.enumerate_bseg_plans(wide, specs=[FP32M]) == []
    with pytest.raises(ValueError):
        plan_bseg(FP32M, 12, 12)


def test_enumeration_unsigned_multiplier_variants():
    layer = planner.matmul_spec("p", 16, 64, 32, w_bits=4, a_bits=4,
                                a_signed=False)
    plans = planner.enumerate_sdv_plans(layer, specs=[INT32])
    assert any(not p.signed_b and p.w_b == 4 for p in plans)
    assert any(p.signed_b and p.w_b == 5 for p in plans)   # w+1 trick
    n_unsigned = max(p.n for p in plans if not p.signed_b)
    n_signed = max(p.n for p in plans if p.signed_b)
    assert n_unsigned >= n_signed          # the unsigned domain packs denser


def test_plan_dict_roundtrip():
    layer = planner.matmul_spec("p", 8, 64, 32, w_bits=4, a_bits=8)
    conv = planner.conv2d_spec("c", 8, 8, 4, 4, 3, 3, w_bits=4, a_bits=4)
    for p in planner.enumerate_plans(layer) + planner.enumerate_plans(conv):
        assert planner.plan_from_dict(planner.plan_to_dict(p)) == p


# ---------------------------------------------------------------------------
# cost model: route-aware scoring
# ---------------------------------------------------------------------------

def test_cost_penalizes_ref_fallbacks():
    layer = planner.matmul_spec("p", 64, 256, 128, w_bits=4, a_bits=8)
    fp32m = plan_sdv(FP32M, 4, 8)
    cost = planner.score_plan(layer, fp32m)
    assert cost.route == "ref" and "fp32" in cost.reason
    assert cost.score >= layer.macs          # naive MACs x penalty
    # the wide datapaths are kernel routes now (word-generic SDV GEMM,
    # two int32 limb planes per wide word) — and at W4A8 they pack 3
    # lanes vs INT32's 2, so the wide word *wins* the layer
    dsp = plan_sdv(DATAPATHS["dsp48e2"], 4, 8, park_sign_bits=True)
    cost48 = planner.score_plan(layer, dsp)
    assert cost48.route == "sdv_matmul", cost48.reason
    int32_cost = planner.score_plan(
        layer, plan_sdv(INT32, 4, 8, park_sign_bits=True))
    assert int32_cost.route == "sdv_matmul"
    assert cost48.score < int32_cost.score < cost.score
    choice = planner.choose_plan(layer)
    assert choice.plan.spec.name in ("dsp48e2", "dsp58")
    assert choice.cost.route == "sdv_matmul"
    assert choice.cost.score <= cost48.score


def test_cost_conv_routes():
    conv = planner.conv2d_spec("c", 32, 32, 16, 32, 3, 3, w_bits=4,
                               a_bits=4)
    bplan = plan_bseg(INT32, 4, 4)
    c = planner.score_plan(conv, bplan)
    assert c.route == "bseg_conv2d"
    assert c.wide_multiplies > 0 and c.density > 1
    # w_i > 7 conv plans cannot stage int8 -> ref
    wide_act = planner.conv2d_spec("c", 8, 8, 4, 4, 3, 3, w_bits=2,
                                   a_bits=8)
    b8 = plan_bseg(INT32, 2, 8)
    assert planner.score_plan(wide_act, b8).route == "ref"
    # head-like 1x1: the GEMM shape wins on SDV
    head = planner.conv2d_spec("h", 8, 8, 64, 36, 1, 1, w_bits=4, a_bits=4)
    hc = planner.choose_plan(head)
    assert isinstance(hc.plan, SDVPlan) and hc.cost.route == "im2col"


def test_no_int32_default_still_plans_and_renders():
    """Bit configs the INT32 default cannot pack must still plan,
    render in the table, and count as differing — not crash."""
    layer = planner.matmul_spec("p", 8, 48, 32, w_bits=16, a_bits=16)
    assert planner.default_plan_for(layer) is None
    choice = planner.choose_plan(layer)
    assert planner.plan_differs_from_default(choice)
    table = planner.format_plan_table([choice])
    assert "dsp" in table          # only the wide FPGA words fit W16A16
    with pytest.raises(ValueError, match="no INT32 default"):
        planner.plan_layers([layer], policy="default")


def test_conv1d_route_selector_shared_gates():
    assert ops.select_conv1d_route(plan_bseg(INT32, 4, 4)) == "bseg_conv1d"
    # the conv kernels are word-generic: the wide DSP words run as two
    # int32 limb planes on the kernel route — no x64 involved
    route, reason = ops.select_conv1d_route(
        plan_bseg(DATAPATHS["dsp48e2"], 4, 4), explain=True)
    assert route == "bseg_conv1d" and "dsp48e2" in reason
    route, reason = ops.select_conv1d_route(plan_bseg(INT32, 4, 4),
                                            use_kernel=False, explain=True)
    assert route == "ref"
    # w_i > 7 still cannot stage int8 activations
    route, reason = ops.select_conv1d_route(
        plan_bseg(DATAPATHS["dsp48e2"], 2, 8), explain=True)
    assert route == "ref" and "int8" in reason
    # the planner cost model goes through the same selector
    layer = planner.conv1d_spec("c", 32, 4, w_bits=4, a_bits=4)
    cost = planner.score_plan(layer, plan_bseg(DATAPATHS["dsp58"], 4, 4))
    assert cost.route == "bseg_conv1d" and cost.density > 1


def test_choose_plan_deterministic_and_alternatives():
    layer = planner.matmul_spec("p", 8, 128, 64, w_bits=4, a_bits=8)
    a = planner.choose_plan(layer, top_k=3)
    b = planner.choose_plan(layer, top_k=3)
    assert a.plan == b.plan and len(a.alternatives) == 2
    with pytest.raises(ValueError):
        # 20-bit weights fit no datapath at all
        planner.choose_plan(planner.matmul_spec("x", 8, 8, 8, w_bits=40,
                                                a_bits=40))


def test_route_explain_tuples():
    p = plan_sdv(INT32, 4, 8, park_sign_bits=True)
    route, reason = ops.select_packed_route(64, plan=p, explain=True)
    assert route == "sdv_matmul" and "GEMV_MAX_ROWS" in reason
    route, reason = ops.select_conv_route(
        (1, 8, 8, 3), (16, 3, 3, 3), plan=plan_bseg(INT32, 4, 4),
        explain=True)
    assert route == "bseg_conv2d"
    # wide-word datapaths run on the word-generic MATMUL kernels (two
    # int32 limb planes — no x64); fp32m still refuses — rounding
    # breaks SDV spill tracking
    dsp = plan_sdv(DATAPATHS["dsp58"], 4, 8, park_sign_bits=True)
    route, reason = ops.select_packed_route(64, plan=dsp, explain=True)
    assert route == "sdv_matmul" and "GEMV_MAX_ROWS" in reason
    assert ops.select_packed_route(64, plan=dsp, mode="sdv_matmul") \
        == "sdv_matmul"
    with pytest.raises(ValueError, match="fp32"):
        ops.select_packed_route(64, plan=plan_sdv(FP32M, 4, 8),
                                mode="sdv_matmul")
    # ... while the CONV side runs them on the word-generic kernels
    bdsp = plan_bseg(DATAPATHS["dsp48e2"], 4, 4)
    route, reason = ops.select_conv_route((1, 8, 8, 3), (16, 3, 3, 3),
                                          plan=bdsp, explain=True)
    assert route == "bseg_conv2d" and "dsp48e2" in reason
    assert ops.select_conv_route((1, 8, 8, 3), (16, 3, 3, 3), plan=bdsp,
                                 mode="bseg_conv2d") == "bseg_conv2d"


# ---------------------------------------------------------------------------
# bit-exactness of planner-chosen plans (UltraNet layer shapes)
# ---------------------------------------------------------------------------

def test_planned_ultranet_layers_bit_exact():
    """Every planner-chosen per-layer plan (mixed precision: 8-bit
    first layer) must stay bit-exact vs the integer conv oracle."""
    from repro.models import ultranet as U
    choices = planner.plan_ultranet(16, first_layer_a_bits=8)
    base = plan_bseg(INT32, U.W_BITS, U.A_BITS)
    shapes = U.ultranet_layer_shapes(16, 16)
    assert len(choices) == len(shapes)
    for s, c in zip(shapes, choices):
        x = jnp.asarray(RNG.integers(0, 16, (1, s["h"], s["w"], s["cin"])),
                        jnp.int32)
        w = jnp.asarray(RNG.integers(-8, 8,
                                     (s["cout"], s["cin"], s["k"], s["k"])),
                        jnp.int8)
        want = np.asarray(ref.conv2d_int_ref(x, w))
        got = U._conv2d_planned(x, w, c, base)
        assert (np.asarray(got) == want).all(), (c.layer.name, c.plan)


def test_planned_ultranet_forward_end_to_end():
    from repro.models import ultranet as U
    params = U.init_ultranet(0)
    img = jnp.asarray(RNG.integers(0, 16, (1, 16, 16, 3)), jnp.int32)
    choices = planner.plan_ultranet(16, first_layer_a_bits=8)
    y_ref = U.ultranet_forward(params, img, mode="ref")
    y_pl = U.ultranet_forward(params, img, mode="bseg", plans=choices)
    assert (np.asarray(y_ref) == np.asarray(y_pl)).all()
    with pytest.raises(ValueError):       # plans need mode="bseg"
        U.ultranet_forward(params, img, mode="ref", plans=choices)
    with pytest.raises(ValueError):       # one plan per conv
        U.ultranet_forward(params, img, mode="bseg", plans=choices[:3])


def test_planned_ultranet_differs_from_default():
    """The PR acceptance criterion: at least one layer's chosen
    (datapath, packing factor) differs from the uniform default."""
    choices = planner.plan_ultranet(64, first_layer_a_bits=8)
    assert any(planner.plan_differs_from_default(c) for c in choices)
    # the mixed-precision first layer cannot keep the W4A4 default plan
    assert planner.plan_differs_from_default(choices[0])


def test_packed_conv2d_sdv_plan_override():
    x = jnp.asarray(RNG.integers(0, 16, (1, 6, 7, 5)), jnp.int32)
    w = jnp.asarray(RNG.integers(-8, 8, (9, 5, 3, 3)), jnp.int8)
    base = plan_bseg(INT32, 4, 4)
    override = plan_sdv(INT32, 4, 4, signed_a=True, signed_b=False,
                        park_sign_bits=True)
    want = np.asarray(ref.conv2d_int_ref(x, w))
    got = ops.packed_conv2d(x, w, plan=base, mode="im2col",
                            sdv_plan=override)
    assert (np.asarray(got) == want).all()
    with pytest.raises(ValueError):   # unsigned override needs zp == 0
        ops.packed_conv2d(x, w, plan=base, mode="im2col",
                          sdv_plan=override, zero_point=8)


# ---------------------------------------------------------------------------
# serve_params plan policies
# ---------------------------------------------------------------------------

def _serve_tree():
    return {
        "layer": {"kernel": jnp.asarray(
            RNG.standard_normal((96, 40)), jnp.float32)},
        "lm_head": jnp.asarray(RNG.standard_normal((64, 128)), jnp.float32),
    }


def _assert_sdv_leaf_bit_exact(leaf):
    """The packed GEMM on a routed layer == the integer ref oracle."""
    w_int = np.asarray(ref.sdv_unpack_words_ref(leaf.words, plan=leaf.plan))
    # words are [K, G] for 1-limb plans, [2, K, G] limb planes for the
    # wide words: K is shape[-2] either way
    d_in = leaf.words.shape[-2]
    lim = 1 << (leaf.plan.w_b - 1)
    xq = jnp.asarray(RNG.integers(-lim, lim, (12, d_in)), jnp.int32)
    y = ops.packed_matmul(xq, leaf.words, plan=leaf.plan, m=leaf.d_out)
    want = np.asarray(xq) @ w_int[:, :leaf.d_out]
    assert (np.asarray(y) == want).all(), leaf.plan


def test_serve_params_plan_policy_auto_bit_exact():
    from repro.models.quantized import SDVLinear, serve_params
    qp = serve_params(_serve_tree(), bits=4, min_size=1, compute="sdv",
                      plan_policy="auto")
    leaves = [qp["layer"]["kernel"], qp["lm_head"]]
    assert all(isinstance(v, SDVLinear) for v in leaves)
    for leaf in leaves:
        # planner choices must land on a kernel route (wide words
        # included — the W4A8 winner is a DSP emulation word now)
        assert leaf.plan.spec.exact_wrap
        route = ops.select_packed_route(12, plan=leaf.plan)
        assert route in ("sdv_matmul", "sdv_matvec"), leaf.plan
        _assert_sdv_leaf_bit_exact(leaf)
    with pytest.raises(ValueError):
        serve_params(_serve_tree(), compute="sdv", plan_policy="bogus")
    with pytest.raises(ValueError):   # memory packing has no lane plans
        serve_params(_serve_tree(), compute="memory", plan_policy="auto")


def test_serve_params_plan_policy_cache_roundtrip(tmp_path):
    from repro.models.quantized import serve_params
    path = str(tmp_path / "plans.json")
    qp1 = serve_params(_serve_tree(), bits=4, min_size=1, compute="sdv",
                       plan_policy="cache", plan_cache=path)
    payload = json.load(open(path))
    assert payload["version"] == 1
    assert any(k.startswith("choice|matmul:") for k in payload["entries"])
    qp2 = serve_params(_serve_tree(), bits=4, min_size=1, compute="sdv",
                       plan_policy="cache", plan_cache=path)
    assert qp1["lm_head"].plan == qp2["lm_head"].plan


def test_serve_params_warns_on_ref_fallback(monkeypatch):
    """A layer whose best plan still lands on the pure-jnp ref route is
    surfaced, not silently degraded.  With the matmul datapath gap
    closed there is no real bit config that all-refs on this backend
    (every exact-wrap word has a kernel now), so the planner choice is
    doctored to a ref route — the warn path itself is what's under
    test."""
    import dataclasses
    from repro import planner as planner_mod
    from repro.models.quantized import serve_params
    real_choose = planner_mod.choose_plan

    def ref_choice(layer, *a, **kw):
        c = real_choose(layer, *a, **kw)
        return dataclasses.replace(
            c, cost=dataclasses.replace(c.cost, route="ref",
                                        reason="forced ref (test)"))
    monkeypatch.setattr(planner_mod, "choose_plan", ref_choice)
    tree = {"lm_head": jnp.asarray(RNG.standard_normal((48, 32)),
                                   jnp.float32)}
    with pytest.warns(UserWarning, match="ref route"):
        serve_params(tree, bits=4, act_bits=8, min_size=1,
                     compute="sdv", plan_policy="auto")


# ---------------------------------------------------------------------------
# autotune cache
# ---------------------------------------------------------------------------

def test_autotune_layer_uses_cache(tmp_path):
    layer = planner.matmul_spec("p", 4, 32, 16, w_bits=4, a_bits=8)
    cache = planner.PlanCache(path=str(tmp_path / "tune.json"))
    choice = planner.autotune_layer(layer, cache=cache, top_k=2,
                                    repeats=1)
    assert choice.measured_us is not None and choice.measured_us > 0
    cache.save()
    reloaded = planner.PlanCache.load(str(tmp_path / "tune.json"))
    cached = reloaded.get_choice(layer)
    assert cached is not None and cached.plan == choice.plan
    # timings are reused: a second run adds no new timing entries
    n_entries = len(reloaded.entries)
    planner.autotune_layer(layer, cache=reloaded, top_k=2, repeats=1)
    assert len(reloaded.entries) == n_entries


def test_plan_cache_corrupt_file_starts_fresh(tmp_path):
    path = tmp_path / "bad.json"
    path.write_text("{not json")
    cache = planner.PlanCache.load(str(path))
    assert cache.entries == {}


def _synthetic_timing_cache(tmp_path, layer, plans, us_values,
                            use_kernel=True):
    """A PlanCache pre-loaded with timing entries for ``plans`` (no
    kernel ever runs) — the TPU-free autotune fixture."""
    from repro.planner import autotune as at
    cache = planner.PlanCache(path=str(tmp_path / "tune.json"))
    backend = at._backend()
    for plan, us in zip(plans, us_values):
        route, _ = planner.route_for(layer, plan, use_kernel)
        cache.entries[planner.timing_key(layer, plan, backend)] = {
            "us": us, "plan": planner.plan_to_dict(plan), "route": route}
    return cache


def test_autotune_tiebreaks_ultranet_body_by_measured_time(tmp_path):
    """ROADMAP item 'planner wall-clock calibration': when a cache
    supplies timings, the UltraNet 3x3 body choice follows measured
    time, not the analytic score — including overturning the analytic
    winner — without touching a TPU (every shortlist timing is a
    synthetic cache hit, so no kernel runs)."""
    layer = planner.ultranet_layer_specs(32)[2]       # a 3x3 body conv
    assert layer.kh == layer.kw == 3
    analytic = planner.choose_plan(layer, top_k=3)
    shortlist = planner.timing_shortlist(layer, analytic)
    assert len(shortlist) >= 2
    # make the analytically-WORST shortlisted plan the fastest
    us = [100.0 * (i + 1) for i in range(len(shortlist))][::-1]
    cache = _synthetic_timing_cache(tmp_path, layer, shortlist, us)
    n_before = len(cache.entries)
    choice = planner.autotune_layer(layer, cache=cache, top_k=3,
                                    repeats=1)
    assert choice.plan == shortlist[-1] != analytic.plan
    assert choice.measured_us == min(us)
    # pure cache replay: only the choice| entry was added
    assert len(cache.entries) == n_before + 1
    # and the persisted choice round-trips with its route recorded
    cached = cache.get_choice(layer)
    assert cached is not None and cached.plan == choice.plan
    assert cached.measured_us == choice.measured_us


def test_autotune_shortlist_skips_ref_routed_candidates():
    """Timing shortlists must drop ref-routed candidates whenever a
    kernel-routed candidate with an identical-or-better analytic score
    exists (an interpret-mode ref 'win' would serve no packing at
    all), and keep them when ref is all there is."""
    layer = planner.conv2d_spec("c", 8, 8, 4, 8, 3, 3, w_bits=4, a_bits=4)
    analytic = planner.choose_plan(layer, top_k=3)
    shortlist = planner.timing_shortlist(layer, analytic)
    for plan in shortlist:
        route, _ = planner.route_for(layer, plan)
        assert route != "ref", plan
    # a config where every candidate refs (W12A12 conv: no kernel
    # route exists) keeps its shortlist rather than emptying it
    wide = planner.conv2d_spec("c", 4, 4, 2, 2, 3, 3, w_bits=12,
                               a_bits=12)
    analytic_w = planner.choose_plan(wide, top_k=3)
    short_w = planner.timing_shortlist(wide, analytic_w)
    assert short_w, "all-ref shortlist must not be empty"


def test_plan_cache_invalidates_stale_routes(tmp_path):
    """Cache entries recorded against a route the dispatch no longer
    picks must be invalidated, not replayed — the stale-cache hazard
    when a PR changes routing (e.g. this one closing the conv gap)."""
    from repro.planner import autotune as at
    layer = planner.conv2d_spec("c", 8, 8, 4, 8, 3, 3, w_bits=4, a_bits=4)
    choice = planner.choose_plan(layer)
    backend = at._backend()
    cache = planner.PlanCache(path=str(tmp_path / "stale.json"))
    # a choice entry whose recorded route pretends the plan still refs
    cache.entries[at.choice_key(layer, backend)] = {
        "plan": planner.plan_to_dict(choice.plan),
        "score": choice.cost.score, "route": "ref", "source": "analytic"}
    assert cache.get_choice(layer) is None          # invalidated ...
    assert at.choice_key(layer, backend) not in cache.entries  # ... eagerly
    # a fresh put/get with the live route round-trips
    cache.put_choice(choice, source="analytic", backend=backend)
    got = cache.get_choice(layer)
    assert got is not None and got.plan == choice.plan
    # legacy entries without a recorded route are stale by definition
    cache.entries[at.choice_key(layer, backend)].pop("route")
    assert cache.get_choice(layer) is None


def test_plan_cache_choice_hits_under_use_kernel_false(tmp_path):
    """A choice stored under use_kernel=False (everything refs) must
    hit when read back with the same context — validation must not
    evict entries recorded under a different kernel capability — and
    entries keyed for another backend are returned as recorded."""
    from repro.planner import autotune as at
    layer = planner.conv2d_spec("c", 8, 8, 4, 8, 3, 3, w_bits=4, a_bits=4)
    cache = planner.PlanCache(path=str(tmp_path / "nk.json"))
    choice = planner.choose_plan(layer, use_kernel=False)
    assert choice.cost.route == "ref"
    cache.put_choice(choice, source="analytic")
    assert cache.get_choice(layer, use_kernel=False) is not None
    # ... and plan_layers(policy='cache', use_kernel=False) reuses it
    out = planner.plan_layers([layer], policy="cache", cache=cache,
                              use_kernel=False)
    assert out[0].plan == choice.plan and out[0].cost.route == "ref"
    # cross-backend entries cannot be re-validated here: no eviction
    cache.entries[at.choice_key(layer, "tpu")] = {
        "plan": planner.plan_to_dict(choice.plan),
        "score": choice.cost.score, "route": "bseg_conv2d",
        "source": "autotune"}
    assert cache.get_choice(layer, backend="tpu") is not None


def test_plan_cache_invalidates_stale_wide_word_entries(tmp_path):
    """The stale-cache hazard THIS PR creates: a cache written before
    the two-limb refactor records wide DSP48E2/DSP58 plans on the
    ``ref`` route (the old x64+interpret gate refused them on the
    kernels).  Those entries must invalidate cleanly — the live
    dispatch puts the same plans on SDV kernel routes."""
    from repro.planner import autotune as at
    layer = planner.matmul_spec("m", 4, 64, 48, w_bits=4, a_bits=8)
    choice = planner.choose_plan(layer)
    # the live winner IS a wide word on a kernel route
    assert choice.plan.spec.name in ("dsp48e2", "dsp58"), choice.plan
    assert choice.cost.route in ("sdv_matmul", "sdv_matvec"), choice.cost
    backend = at._backend()
    cache = planner.PlanCache(path=str(tmp_path / "wide.json"))
    cache.entries[at.choice_key(layer, backend)] = {
        "plan": planner.plan_to_dict(choice.plan),
        "score": choice.cost.score, "route": "ref", "source": "analytic"}
    assert cache.get_choice(layer) is None          # stale -> evicted
    assert at.choice_key(layer, backend) not in cache.entries
    # re-recorded under the live route, it round-trips
    cache.put_choice(choice, source="analytic", backend=backend)
    got = cache.get_choice(layer)
    assert got is not None and got.plan == choice.plan
    assert got.cost.route == choice.cost.route


def test_autotune_retimes_stale_timing_entries(tmp_path):
    """A timing entry whose recorded route went stale is re-measured
    (the cached microseconds belong to a different kernel)."""
    from repro.planner import autotune as at
    layer = planner.matmul_spec("p", 4, 24, 12, w_bits=4, a_bits=8)
    analytic = planner.choose_plan(layer, top_k=1)
    backend = at._backend()
    key = planner.timing_key(layer, analytic.plan, backend)
    cache = planner.PlanCache(path=str(tmp_path / "retime.json"))
    cache.entries[key] = {"us": 1e-9,
                          "plan": planner.plan_to_dict(analytic.plan),
                          "route": "ref"}           # stale route
    choice = planner.autotune_layer(layer, cache=cache, top_k=1,
                                    repeats=1)
    assert cache.entries[key]["route"] != "ref"     # re-measured
    assert choice.measured_us is not None and choice.measured_us > 1e-6


# ---------------------------------------------------------------------------
# network adapters + CLI
# ---------------------------------------------------------------------------

def test_plan_layers_policies_and_memoization():
    layers = [planner.matmul_spec(f"l{i}", 8, 128, 64, w_bits=4, a_bits=8)
              for i in range(3)]
    auto = planner.plan_layers(layers, policy="auto")
    assert len(auto) == 3
    assert auto[0].plan == auto[1].plan == auto[2].plan
    assert [c.layer.name for c in auto] == ["l0", "l1", "l2"]
    default = planner.plan_layers(layers, policy="default")
    assert all(isinstance(c.plan, SDVPlan) for c in default)
    with pytest.raises(ValueError):
        planner.plan_layers(layers, policy="bogus")


def test_arch_layer_specs_shape_tree():
    specs = planner.arch_layer_specs("mamba2-130m", smoke=True,
                                     min_size=1024)
    assert specs, "no layers extracted"
    kinds = {s.kind for s in specs}
    assert "conv1d" in kinds          # the SSM short conv is planned too
    for s in specs:
        assert s.macs > 0 and s.key()


def test_cli_main_smoke(tmp_path, capsys):
    from repro.planner.__main__ import main
    out_json = str(tmp_path / "plan.json")
    assert main(["--arch", "ultranet", "--smoke", "--json", out_json]) == 0
    text = capsys.readouterr().out
    assert "plan table" in text and "MACs/multiply" in text
    payload = json.load(open(out_json))
    assert len(payload["layers"]) == 9
    assert any(l["differs_from_default"] for l in payload["layers"])


def test_cli_main_no_x64(tmp_path, capsys):
    """The planner CLI must not force-enable x64 (the wide words run
    as two int32 limb planes): under ``jax.enable_x64(False)`` the table still
    builds, x64 stays off afterwards, and every wide-datapath layer
    the table prints is priced on a kernel route."""
    import jax
    from repro.planner.__main__ import main
    out_json = str(tmp_path / "plan.json")
    with jax.enable_x64(False):
        assert main(["--arch", "ultranet", "--smoke", "--json",
                     out_json]) == 0
        assert not jax.config.jax_enable_x64, \
            "the CLI re-enabled x64 behind the caller's back"
    capsys.readouterr()
    payload = json.load(open(out_json))
    wide = [l for l in payload["layers"]
            if l["plan"].get("spec") not in ("int32", "fp32m")]
    assert wide, payload["layers"]
    assert all(l["route"] != "ref" for l in wide), wide
