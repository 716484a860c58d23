"""Speculative decoding tests (DESIGN.md §5.2): bitwise equality of
chunked verification vs sequential decode, rollback edge cases
(position 0, across reset_slot, mid-chunked-prefill), engine-level
bit-exactness of speculative vs plain serving, acceptance on a
calibrated checkpoint, the accept-EMA admission blend, degrade-to-
plain-decode semantics, and the loadgen ``drained`` outcome."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import (decode_step, init_cache, init_params,
                          prefill_slot, reset_slot, rollback_slot,
                          serve_params, values, verify_slot, verify_step,
                          Rules)
from repro.serving import BucketShape, Engine
from repro.serving.spec import (SpecConfig, SpecDecoder, accept_length,
                                calibrated_params)


class FakeClock:
    def __init__(self, t=0.0):
        self.t = t

    def __call__(self):
        return self.t

    def advance(self, dt):
        self.t += dt


@pytest.fixture(scope="module")
def tiny_setup():
    from repro.configs.registry import get_arch
    cfg = get_arch("tinyllama-1.1b").reduced()
    rules = Rules(tp=None, fsdp=None, ep=None, batch=())
    params = values(init_params(cfg, rules, jax.random.PRNGKey(0)))
    return cfg, params


@pytest.fixture(scope="module")
def tiny_packed(tiny_setup):
    cfg, params = tiny_setup
    qp = serve_params(params, bits=4, min_size=1024, compute="sdv",
                      act_bits=8, plan_policy="auto", rows=2)
    rules = Rules(tp=None, fsdp=None, ep=None, batch=())
    cache0 = values(init_cache(cfg, rules, 2, 24))
    return cfg, qp, cache0


@pytest.fixture(scope="module")
def calibrated(tiny_setup):
    """A briefly-trained checkpoint: acceptance is a checkpoint
    property, so speculative speedup tests need peaked logits."""
    cfg, _ = tiny_setup
    return calibrated_params(cfg, steps=120, seed=0)


def _toks(rng, vocab, *shape):
    return jnp.asarray(rng.integers(0, vocab, shape), jnp.int32)


# ---------------------------------------------------------------------------
# model layer: verify_step / verify_slot / rollback_slot
# ---------------------------------------------------------------------------

def test_verify_step_matches_sequential_decode(tiny_packed):
    """The exactness pillar: scoring k+1 positions in ONE chunked
    verification wave is bitwise-identical to k+1 sequential decode
    steps — including mixed n_valid (a frozen slot rides along with
    n_valid 0 and must come back untouched)."""
    cfg, qp, cache0 = tiny_packed
    rng = np.random.default_rng(3)
    k1 = 4
    toks = _toks(rng, cfg.vocab, 2, k1)
    nv = jnp.asarray([k1, 0], jnp.int32)          # slot 1 frozen

    vlogits, vcache = verify_step(cfg, qp, cache0, toks, nv)
    vlogits = np.asarray(vlogits)

    cache = cache0
    for j in range(k1):
        logits, cache = decode_step(cfg, qp, cache, toks[:, j:j + 1],
                                    advance=jnp.asarray([1, 0],
                                                        jnp.int32))
        np.testing.assert_array_equal(
            vlogits[0, j], np.asarray(logits)[0, -1])
    assert int(vcache["index"][0]) == k1
    assert int(vcache["index"][1]) == 0
    # the frozen slot's KV is untouched (leaves are [L, B, S, ...]:
    # batch slot is axis 1)
    for name, leaf in vcache.items():
        if name == "index":
            continue
        np.testing.assert_array_equal(np.asarray(leaf)[:, 1],
                                      np.asarray(cache0[name])[:, 1])


def test_verify_slot_matches_and_isolates(tiny_packed):
    """Per-slot verification equals the batched one on that slot and
    leaves every other slot's cache column bit-identical."""
    cfg, qp, cache0 = tiny_packed
    rng = np.random.default_rng(4)
    toks = _toks(rng, cfg.vocab, 2, 3)
    nv = jnp.full((2,), 3, jnp.int32)
    blogits, _ = verify_step(cfg, qp, cache0, toks, nv)
    slogits, scache = verify_slot(cfg, qp, cache0, 0, toks[:1],
                                  nv[:1])
    np.testing.assert_array_equal(np.asarray(slogits)[0],
                                  np.asarray(blogits)[0])
    assert int(scache["index"][0]) == 3
    assert int(scache["index"][1]) == 0
    for name, leaf in scache.items():
        if name == "index":
            continue
        np.testing.assert_array_equal(np.asarray(leaf)[:, 1],
                                      np.asarray(cache0[name])[:, 1])


def test_rollback_clamps_at_zero(tiny_packed):
    """Rolling back past position 0 clamps (a fresh slot asked to
    rewind is a no-op, not a negative index)."""
    _, _, cache0 = tiny_packed
    c = rollback_slot(cache0, 0, 5)
    assert int(c["index"][0]) == 0 and int(c["index"][1]) == 0


def test_rollback_then_redecode_bit_exact(tiny_packed):
    """The soundness pillar: advance a slot k+1 speculative positions,
    roll the rejected tail back, and decode again — logits and the
    final cache index must be bitwise-identical to a cache that never
    speculated.  Stale KV beyond the index is unreachable (reads are
    position-masked) and overwritten by the next write."""
    cfg, qp, cache0 = tiny_packed
    rng = np.random.default_rng(5)
    toks = _toks(rng, cfg.vocab, 2, 4)
    adv = jnp.ones((2,), jnp.int32)

    # speculated: consume 4, reject the last 3, then re-decode them
    _, spec = verify_step(cfg, qp, cache0, toks,
                          jnp.full((2,), 4, jnp.int32))
    spec = rollback_slot(rollback_slot(spec, 0, 3), 1, 3)
    # control: only ever consumed the single accepted token
    _, ctrl = decode_step(cfg, qp, cache0, toks[:, :1], advance=adv)

    for j in range(1, 4):
        ls, spec = decode_step(cfg, qp, spec, toks[:, j:j + 1],
                               advance=adv)
        lc, ctrl = decode_step(cfg, qp, ctrl, toks[:, j:j + 1],
                               advance=adv)
        np.testing.assert_array_equal(np.asarray(ls), np.asarray(lc))
    np.testing.assert_array_equal(np.asarray(spec["index"]),
                                  np.asarray(ctrl["index"]))


def test_rollback_across_reset_slot(tiny_packed):
    """A freed slot's reset must erase speculative history: rollback
    then reset_slot yields decode bit-identical to a pristine cache
    (the mid-wave join path when the leaving slot was speculating)."""
    cfg, qp, cache0 = tiny_packed
    rng = np.random.default_rng(6)
    toks = _toks(rng, cfg.vocab, 2, 4)
    _, used = verify_step(cfg, qp, cache0, toks,
                          jnp.full((2,), 4, jnp.int32))
    used = rollback_slot(used, 0, 2)
    joined = reset_slot(used, 0)
    assert int(joined["index"][0]) == 0

    fresh = _toks(rng, cfg.vocab, 2, 2)
    adv = jnp.asarray([1, 0], jnp.int32)          # slot 1 frozen
    a, b = joined, cache0
    for j in range(2):
        la, a = decode_step(cfg, qp, a, fresh[:, j:j + 1], advance=adv)
        lb, b = decode_step(cfg, qp, b, fresh[:, j:j + 1], advance=adv)
        np.testing.assert_array_equal(np.asarray(la)[0],
                                      np.asarray(lb)[0])


def test_rollback_mid_chunked_prefill(tiny_packed):
    """A speculating slot rolls back while its neighbour is mid
    chunked prefill: the neighbour's replay and subsequent decode must
    be bit-identical to a never-speculated cache."""
    cfg, qp, cache0 = tiny_packed
    rng = np.random.default_rng(7)
    prompt = _toks(rng, cfg.vocab, 1, 8)
    spec_toks = _toks(rng, cfg.vocab, 2, 4)

    def half_prefill(cache):
        return prefill_slot(cfg, qp, cache, 0, prompt[:, :4],
                            jnp.asarray([4], jnp.int32))

    # speculated path: slot 0 halfway through prefill, slot 1 verifies
    # 4 positions and rejects 3 of them
    spec = half_prefill(cache0)
    _, spec = verify_step(cfg, qp, spec, spec_toks,
                          jnp.asarray([0, 4], jnp.int32))
    spec = rollback_slot(spec, 1, 3)
    # control path: slot 1 consumed only the accepted token
    ctrl = half_prefill(cache0)
    _, ctrl = decode_step(cfg, qp, ctrl, spec_toks[:, :1],
                          advance=jnp.asarray([0, 1], jnp.int32))

    # both finish slot 0's prefill, then decode both slots
    spec = prefill_slot(cfg, qp, spec, 0, prompt[:, 4:],
                        jnp.asarray([4], jnp.int32))
    ctrl = prefill_slot(cfg, qp, ctrl, 0, prompt[:, 4:],
                        jnp.asarray([4], jnp.int32))
    step = _toks(rng, cfg.vocab, 2, 1)
    adv = jnp.ones((2,), jnp.int32)
    ls, spec = decode_step(cfg, qp, spec, step, advance=adv)
    lc, ctrl = decode_step(cfg, qp, ctrl, step, advance=adv)
    np.testing.assert_array_equal(np.asarray(ls), np.asarray(lc))
    np.testing.assert_array_equal(np.asarray(spec["index"]),
                                  np.asarray(ctrl["index"]))


# ---------------------------------------------------------------------------
# SpecDecoder / SpecConfig
# ---------------------------------------------------------------------------

def test_spec_config_validates():
    with pytest.raises(ValueError, match="spec_k"):
        SpecConfig(k=0)


def test_spec_decoder_rejects_recurrent_families(tiny_setup):
    from repro.configs.registry import get_arch
    _, params = tiny_setup
    ssm = get_arch("mamba2-130m").reduced()
    with pytest.raises(ValueError, match="family"):
        SpecDecoder(ssm, params)


def test_accept_length():
    assert accept_length(np.array([5, 6, 7]), np.array([5, 6, 7, 9])) == 3
    assert accept_length(np.array([5, 6, 7]), np.array([5, 9, 7, 9])) == 1
    assert accept_length(np.array([5, 6, 7]), np.array([1, 6, 7, 9])) == 0


def test_draft_strictly_denser(tiny_setup):
    """The density pillar: every draft GEMM resolves to a strictly
    higher packing density than the target on the SAME datapath
    (W4A4 vs W4A8 — the activation width is the knob, see
    serving.spec)."""
    cfg, params = tiny_setup
    dec = SpecDecoder(cfg, params, SpecConfig(), plan_policy="auto")
    tqp = serve_params(params, bits=4, min_size=1024, compute="sdv",
                       act_bits=8, plan_policy="auto", rows=4)
    rows = dec.plan_comparison(tqp, 4)
    assert rows and all(r["draft_denser"] for r in rows)


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

def _serve(cfg, params, *, speculative, prefill_chunk=4, n=6, seed=11,
           **kw):
    eng = Engine(cfg, params, buckets=(BucketShape(4, 64),),
                 speculative=speculative, prefill_chunk=prefill_chunk,
                 **kw)
    rng = np.random.default_rng(seed)
    rids = []
    for i in range(n):
        p = [int(x) for x in rng.integers(0, cfg.vocab, 3 + i % 5)]
        rids.append(eng.submit(p, new_tokens=4 + i % 4))
    eng.drain()
    toks = {c.rid: c.tokens for c in eng.completions}
    return [toks[r] for r in rids], eng


def test_engine_spec_bit_exact_random(tiny_setup):
    """Random-init params: acceptance is ~0, so this is the rollback-
    heavy path — every round rejects almost everything, and output
    must STILL be bit-identical to plain decode."""
    cfg, params = tiny_setup
    plain, _ = _serve(cfg, params, speculative=False)
    spec, eng = _serve(cfg, params, speculative=True)
    assert plain == spec
    sp = eng.metrics.snapshot()["speculative"]
    assert sp["rounds"] > 0 and sp["degraded_buckets"] == 0


def test_engine_spec_bit_exact_chunk1(tiny_setup):
    """prefill_chunk=1: spec mode still forces prompt replay through
    the chunked-prefill path (a speculative round must never race
    teacher forcing), and output stays bit-exact."""
    cfg, params = tiny_setup
    plain, _ = _serve(cfg, params, speculative=False, prefill_chunk=1)
    spec, eng = _serve(cfg, params, speculative=True, prefill_chunk=1)
    assert plain == spec
    assert eng.metrics.snapshot()["speculative"]["rounds"] > 0


def test_engine_spec_accepts_on_calibrated(tiny_setup, calibrated):
    """On a briefly-trained checkpoint the W4A4 draft agrees with the
    W4A8 target: mean accepted tokens per round must beat plain
    decode's 1, and output is still bit-identical."""
    cfg, _ = tiny_setup
    plain, _ = _serve(cfg, calibrated, speculative=False)
    spec, eng = _serve(cfg, calibrated, speculative=True)
    assert plain == spec
    sp = eng.metrics.snapshot()["speculative"]
    assert sp["mean_accepted"] > 1.0
    assert any(int(k) >= 2 for k in sp["acceptance_hist"])
    st = eng._states["b4.s64"]
    assert st.accept_ema > 1.0          # _end_wave folded the rate


def test_engine_spec_degrades_to_plain_decode(tiny_setup):
    """DESIGN.md §5.2 degrade semantics: a draft runtime failure turns
    speculation OFF for the bucket and serves the same wave with plain
    decode on the SAME bucket — no quarantine, no batch-1 fallback,
    and output stays bit-exact."""
    cfg, params = tiny_setup
    plain, _ = _serve(cfg, params, speculative=False)

    eng = Engine(cfg, params, buckets=(BucketShape(4, 64),),
                 speculative=True, prefill_chunk=4)
    eng.warmup(BucketShape(4, 64))
    assert eng._states["b4.s64"].spec_on

    def boom(*a, **kw):
        raise RuntimeError("draft device fault")
    eng.spec.draft = boom

    rng = np.random.default_rng(11)
    rids = []
    for i in range(6):
        p = [int(x) for x in rng.integers(0, cfg.vocab, 3 + i % 5)]
        rids.append(eng.submit(p, new_tokens=4 + i % 4))
    with pytest.warns(UserWarning, match="degrading to plain decode"):
        eng.drain()
    toks = {c.rid: c.tokens for c in eng.completions}
    assert [toks[r] for r in rids] == plain
    snap = eng.metrics.snapshot()
    assert snap["speculative"]["degraded_buckets"] == 1
    assert snap["faults"]["fallback_waves"] == 0
    assert snap["faults"]["quarantines"] == 0
    assert not eng._states["b4.s64"].spec_on
    assert all(o["outcome"] == "ok" for o in eng.outcomes.values())


def test_est_wave_s_blends_accept_ema(tiny_setup):
    """The admission satellite, pinned in BOTH directions: a
    speculating bucket's wave estimate divides the round-priced decode
    EMA by the acceptance EMA; a non-speculating (or degraded) bucket
    keeps the plain estimate."""
    cfg, params = tiny_setup
    clock = FakeClock()
    eng = Engine(cfg, params, speculative=True, clock=clock,
                 buckets=(BucketShape(2, 21),))
    st = eng._state(BucketShape(2, 21))
    st.warmed, st.decode_s = True, 0.01           # 0.2 s plain estimate
    st.spec_on, st.accept_ema = True, 4.0
    assert eng._est_wave_s() == pytest.approx(0.05)   # 0.2 / 4
    st.spec_on = False                            # degraded: no blend
    assert eng._est_wave_s() == pytest.approx(0.2)
    st.spec_on, st.accept_ema = True, 0.0         # no data yet: no blend
    assert eng._est_wave_s() == pytest.approx(0.2)
    # and a plain engine never blends even with a (stale) accept_ema
    plain = Engine(cfg, params, clock=clock,
                   buckets=(BucketShape(2, 21),))
    pst = plain._state(BucketShape(2, 21))
    pst.warmed, pst.decode_s, pst.accept_ema = True, 0.01, 4.0
    assert plain._est_wave_s() == pytest.approx(0.2)


def test_spec_report_schema(tiny_setup):
    cfg, params = tiny_setup
    _, eng = _serve(cfg, params, speculative=True)
    rep = eng.spec_report()
    assert rep
    for v in rep.values():
        assert v["spec_on"] is True
        assert all(l["draft_denser"] for l in v["layers"])
    # a plain engine reports nothing
    plain = Engine(cfg, params, buckets=(BucketShape(4, 64),))
    assert plain.spec_report() == {}


# ---------------------------------------------------------------------------
# loadgen: the ``drained`` outcome
# ---------------------------------------------------------------------------

def test_loadgen_drained_outcome(tiny_setup):
    """EngineDraining is terminal for the client: distinct ``drained``
    outcome, never retried like Backpressure (it subclasses it, so the
    except order matters)."""
    from repro.serving.loadgen import run_poisson
    cfg, params = tiny_setup
    eng = Engine(cfg, params, buckets=(BucketShape(4, 64),))
    eng._admitting = False              # a drain is in progress
    snap = run_poisson(eng, rate=80.0, duration_s=0.1, prompt_len=4,
                       new_tokens=2, rng=np.random.default_rng(0),
                       retries=3)
    counts = snap["client_outcomes"]
    assert counts["drained"] == snap["offered_requests"] > 0
    assert counts["rejected"] == 0
    assert snap["retried_submissions"] == 0     # never retried


def test_engine_spec_ends_request_prefill_span(tiny_setup):
    """The speculative path opens no engine spans of its own, but a
    request's ``request.prefill`` span ends with its first tokens, not
    when the request completes."""
    from repro.serving.metrics import SPANS
    cfg, params = tiny_setup
    eng = Engine(cfg, params, buckets=(BucketShape(4, 64),),
                 speculative=True, prefill_chunk=4)
    SPANS.start()
    try:
        rid = eng.submit([1, 2, 3, 4, 5], new_tokens=8)
        live = []
        for _ in range(8):
            eng.step(force=True)
            live = [s for _, s in eng._states["b4.s64"].sessions.active()]
            if not live or live[0].tokens:
                break
        assert live and live[0].tokens and not live[0].done()
        pre = [r for r in SPANS.records() if r.name == "request.prefill"]
        assert [r.rid for r in pre] == [rid] and pre[0].end_ns is not None
        assert eng.metrics.snapshot()["speculative"]["rounds"] > 0
        eng.drain()
    finally:
        SPANS.stop()
    assert eng.outcomes[rid]["outcome"] == "ok"
