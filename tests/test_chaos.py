"""Chaos harness: seeded fault injection across the serving stack
(DESIGN.md §5 failure modes).

Invariants swept here:
  * zero lost requests — every admitted request reaches exactly one
    terminal outcome (``ok | shed | failed``) under every fault class;
  * completed outputs are bit-exact vs a fault-free single-request run
    in the same bucket shape (retries and re-routes never change
    tokens — decode is deterministic, lane plans never change
    arithmetic);
  * a quarantined bucket demonstrably recovers: after its cooldown it
    serves waves on its own shape again (``recoveries`` > 0);
  * corrupt plan caches demote ``plan_policy="cache"`` to ``"auto"``
    with a warning, never an exception;
  * malformed submissions are rejected cleanly (typed ValueError, a
    ``requests_malformed`` counter, no queue mutation).

Everything runs on a FakeClock — cooldowns, deadlines, backoff and the
Poisson driver all advance simulated time, so the suite is fully
deterministic and sleep-free.
"""
import json

import jax
import numpy as np
import pytest

from repro.planner import PlanCache, PlanCacheCorrupt
from repro.serving import (Backpressure, BucketShape, Engine,
                           EngineDraining, FaultPlan, InjectedFault,
                           Request, corrupt_json_file)
from repro.serving.engine import FALLBACK_KEY
from repro.serving.loadgen import (_request_specs, poisson_arrivals,
                                   run_poisson)


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
    from repro.models import init_params, values, Rules
    cfg = get_arch("tinyllama-1.1b").reduced()
    rules = Rules(tp=None, fsdp=None, ep=None, batch=())
    params = values(init_params(cfg, rules, jax.random.PRNGKey(0)))
    return cfg, params


# ---------------------------------------------------------------------------
# the fault plan itself (no jax, no engine)
# ---------------------------------------------------------------------------

def test_fault_plan_deterministic():
    """Same seed + same call sequence -> identical fault schedule."""
    def drive(plan):
        for _ in range(3):
            try:
                plan.maybe_fail_compile("b2.s16")
            except InjectedFault:
                pass
        for i in range(10):
            plan.begin_wave("b2.s16", 8)
            plan.draw_malformed()
        return list(plan.log)

    a = drive(FaultPlan.chaos(seed=3))
    b = drive(FaultPlan.chaos(seed=3))
    assert a == b and a                       # non-empty and identical
    assert drive(FaultPlan.chaos(seed=4)) != a


def test_fault_plan_chaos_classes_validated():
    with pytest.raises(ValueError, match="unknown fault classes"):
        FaultPlan.chaos(0, classes=("compile_fail", "bogus"))
    narrowed = FaultPlan.chaos(0, classes=("kernel_loss",))
    assert narrowed.kernel_loss_p > 0
    assert narrowed.compile_failures == {} and narrowed.malformed_p == 0


def test_malformed_request_shapes():
    """Every malformed draw is rejected by the admission layer: empty
    prompts and zero budgets fail ``Request`` validation, unfittable
    prompts fail bucket assignment."""
    from repro.serving import bucket_for
    plan = FaultPlan(seed=1, malformed_p=1.0)
    assert plan.draw_malformed()
    buckets = (BucketShape(2, 32),)
    seen = set()
    for _ in range(30):
        prompt, nt = plan.malformed_request(vocab=50, too_long=64)
        kind = plan.log[-1][1]
        seen.add(kind)
        if kind == "unfittable":
            with pytest.raises(ValueError, match="largest bucket"):
                bucket_for(Request(prompt, nt), buckets)
        else:
            with pytest.raises(ValueError):
                Request(prompt, nt)
    assert seen == {"empty", "zero_budget", "unfittable"}


def test_corrupt_json_file_and_plan_cache(tmp_path):
    path = tmp_path / "plans.json"
    path.write_text(json.dumps({"version": 1, "entries": {}}))
    corrupt_json_file(str(path), seed=0)
    # garbled beyond JSON (and beyond utf-8 — junk bytes included)
    with pytest.raises(ValueError):
        json.loads(path.read_bytes().decode("utf-8", errors="strict"))
    # lenient load starts fresh; strict load raises the typed error
    assert PlanCache.load(str(path)).entries == {}
    with pytest.raises(PlanCacheCorrupt):
        PlanCache.load(str(path), strict=True)
    # wrong version is corruption too (schema it cannot trust)
    path.write_text(json.dumps({"version": 999, "entries": {}}))
    with pytest.raises(PlanCacheCorrupt, match="version"):
        PlanCache.load(str(path), strict=True)


# ---------------------------------------------------------------------------
# engine-level degradation (fake clock, deterministic)
# ---------------------------------------------------------------------------

def _engine(cfg, params, clock, *, buckets, faults=None, threshold=2,
            cooldown=1.0, **kw):
    return Engine(cfg, params, compute="sdv", buckets=buckets,
                  clock=clock, breaker_threshold=threshold,
                  breaker_cooldown_s=cooldown, faults=faults, **kw)


def test_corrupt_plan_cache_demotes_to_auto(tiny_setup, tmp_path):
    cfg, params = tiny_setup
    cache = tmp_path / "plans.json"
    cache.write_text(json.dumps({"version": 1, "entries": {}}))
    corrupt_json_file(str(cache), seed=0)
    with pytest.warns(UserWarning, match="plan cache unusable"):
        eng = Engine(cfg, params, compute="sdv", plan_policy="cache",
                     plan_cache=str(cache))
    assert eng.plan_policy == "auto"          # degraded, not dead


def test_malformed_rejected_cleanly(tiny_setup):
    cfg, params = tiny_setup
    clock = FakeClock()
    eng = _engine(cfg, params, clock, buckets=(BucketShape(2, 16),))
    with pytest.raises(ValueError, match="malformed"):
        eng.submit((), 4)                     # empty prompt
    with pytest.raises(ValueError, match="malformed"):
        eng.submit((1, 2), 0)                 # zero decode budget
    with pytest.raises(ValueError, match="malformed"):
        eng.submit(None, 4)                   # not a sequence at all
    with pytest.raises(ValueError, match="largest bucket"):
        eng.submit(tuple(range(100)), 4)      # unfittable
    assert eng.metrics.snapshot()["requests_malformed"] == 3
    assert eng.depth() == 0 and eng.outcomes == {}


def test_deadline_shed_records_outcome(tiny_setup):
    cfg, params = tiny_setup
    clock = FakeClock()
    eng = _engine(cfg, params, clock, buckets=(BucketShape(2, 16),))
    rid = eng.submit((1, 2, 3), 2, deadline=clock() + 5.0)
    clock.advance(6.0)                        # expired while queued
    assert eng.step() == []                   # shed, no wave burned
    assert eng.outcomes[rid] == {"outcome": "shed",
                                 "detail": "deadline_exceeded"}
    snap = eng.metrics.snapshot()
    assert snap["requests_shed"] == 1 and snap["waves"]["count"] == 0
    assert eng.depth() == 0


def test_drain_close_blocks_admission(tiny_setup):
    cfg, params = tiny_setup
    clock = FakeClock()
    eng = _engine(cfg, params, clock, buckets=(BucketShape(2, 16),))
    eng.drain(close=True)                     # empty drain, then shut
    with pytest.raises(EngineDraining):
        eng.submit((1, 2, 3), 2)
    # a non-closing drain leaves admission open
    eng2 = _engine(cfg, params, clock, buckets=(BucketShape(2, 16),))
    eng2.drain()
    rid = eng2.submit((1, 2, 3), 2)
    assert rid == 0


def test_circuit_breaker_quarantine_reroute_recover(tiny_setup):
    """The full breaker arc on one bucket: two injected compile
    failures quarantine it, its requests re-route to the next healthy
    shape (and complete there), the cooldown turns it probing, and the
    probe wave restores it to healthy — it serves on its own shape
    again."""
    cfg, params = tiny_setup
    clock = FakeClock()
    faults = FaultPlan(seed=0, compile_failures={"b2.s16": 2})
    eng = _engine(cfg, params, clock, faults=faults, threshold=2,
                  cooldown=1.0,
                  buckets=(BucketShape(2, 16), BucketShape(2, 32)))
    r0 = eng.submit((1, 2, 3), 2)
    r1 = eng.submit((4, 5, 6), 2)
    assert eng.step() == []                   # injected compile fail #1
    assert eng.bucket_health()["b2.s16"] == "healthy"   # below threshold
    assert eng.step() == []                   # fail #2 -> quarantine
    assert eng.bucket_health()["b2.s16"] == "quarantined"
    assert eng.metrics.quarantines == 1
    assert eng.metrics.rerouted == 2          # both re-routed
    comps = {c.rid: c for c in eng.drain()}
    assert sorted(comps) == [r0, r1]          # nothing lost
    assert all(c.bucket_key == "b2.s32" for c in comps.values())
    assert all(eng.outcomes[r]["outcome"] == "ok" for r in (r0, r1))
    # cooldown -> probing -> successful probe wave -> healthy
    clock.advance(1.5)
    assert eng.step() == []                   # tick breakers: reinstate
    assert eng.bucket_health()["b2.s16"] == "probing"
    r2 = eng.submit((7, 8, 9), 2)
    comps = {c.rid: c for c in eng.drain()}
    assert comps[r2].bucket_key == "b2.s16"   # served on its own shape
    assert eng.bucket_health()["b2.s16"] == "healthy"
    assert eng.metrics.recoveries == 1
    assert faults.counts() == {"compile_fail": 2}


def test_kernel_loss_falls_back_and_completes(tiny_setup):
    """Every wave loses its kernel route mid-flight: the bucket
    quarantines after the threshold and the fault-exempt fallback path
    serves everything — zero lost, all outcomes ``ok``, tokens
    bit-exact vs a fault-free run in the fallback's own shape."""
    cfg, params = tiny_setup
    clock = FakeClock()
    faults = FaultPlan(seed=0, kernel_loss_p=1.0)
    eng = _engine(cfg, params, clock, faults=faults, threshold=2,
                  buckets=(BucketShape(2, 16),))
    specs = [((1, 2, 3), 3), ((4, 5, 6, 7), 2)]
    rids = [eng.submit(p, nt) for p, nt in specs]
    comps = {c.rid: c for c in eng.drain()}
    assert sorted(comps) == sorted(rids)
    assert all(eng.outcomes[r]["outcome"] == "ok" for r in rids)
    assert eng.metrics.failure_kinds.get("kernel_loss", 0) >= 2
    assert eng.metrics.fallback_waves == len(rids)
    fb_shape = eng._states[FALLBACK_KEY].bucket
    assert all(c.bucket_key == fb_shape.key for c in comps.values())
    # occupancy counts the failed waves' iterations; the wave counts
    # (count, steps, wall_s) hold only the waves that ended well
    failed = eng.metrics.snapshot()["buckets"]["b2.s16"]
    assert failed["waves"] == failed["steps"] == 0
    assert failed["slot_steps"] > 0
    assert failed["busy_slot_steps"] <= failed["slot_steps"]
    fb = eng.metrics.snapshot()["buckets"][fb_shape.key]
    assert fb["slot_steps"] == fb_shape.batch * fb["steps"]
    # bit-exact vs fault-free single-request runs in the same shape
    ref = Engine(cfg, params, compute="sdv", buckets=(fb_shape,),
                 plan_policy="default", clock=FakeClock())
    for (p, nt), rid in zip(specs, rids):
        ref_rid = ref.submit(p, nt)
        ref_comp = {c.rid: c for c in ref.drain()}[ref_rid]
        assert ref_comp.tokens == comps[rid].tokens


def test_snapshot_restore_zero_lost(tiny_setup):
    """Engine restart: snapshot the queue, restore into a fresh
    engine, drain — every request completes with its original rid and
    submit_t, tokens bit-exact vs an uninterrupted run, and the rid
    watermark never rolls back."""
    cfg, params = tiny_setup
    buckets = (BucketShape(2, 24),)
    clock_a = FakeClock(100.0)
    a = _engine(cfg, params, clock_a, buckets=buckets)
    specs = [((1, 2, 3), 3), ((4, 5), 2), ((6, 7, 8, 9), 4)]
    rids = [a.submit(p, nt) for p, nt in specs]
    snap = a.snapshot()
    json.loads(json.dumps(snap))              # JSON round-trips
    assert [r["rid"] for r in snap["requests"]] == rids
    b = _engine(cfg, params, FakeClock(200.0), buckets=buckets)
    assert b.restore(snap) == len(specs)
    comps = {c.rid: c for c in b.drain()}
    assert sorted(comps) == sorted(rids)      # zero lost across restart
    for (p, nt), rid in zip(specs, rids):
        assert len(comps[rid].tokens) == nt
        assert comps[rid].submit_t == 100.0   # original latency clock
    assert b.submit((1, 2), 2) == len(specs)  # watermark preserved
    # bit-exact vs an uninterrupted engine
    c = _engine(cfg, params, FakeClock(), buckets=buckets)
    c_rids = [c.submit(p, nt) for p, nt in specs]
    c_comps = {r.rid: r for r in c.drain()}
    for rid, c_rid in zip(rids, c_rids):
        assert comps[rid].tokens == c_comps[c_rid].tokens
    with pytest.raises(ValueError, match="snapshot version"):
        b.restore({"version": 2})


def test_restore_reroutes_unfittable_to_fallback(tiny_setup):
    """A snapshot taken with a larger bucket ladder restores into an
    engine whose ladder cannot hold some requests: those go to the
    degraded fallback queue, not to the floor."""
    cfg, params = tiny_setup
    a = _engine(cfg, params, FakeClock(),
                buckets=(BucketShape(2, 16), BucketShape(2, 48)))
    small = a.submit((1, 2, 3), 2)
    big = a.submit(tuple(range(30)), 4)       # needs s48
    snap = a.snapshot()
    b = _engine(cfg, params, FakeClock(), buckets=(BucketShape(2, 16),))
    assert b.restore(snap) == 2
    assert len(b._fallback_pending) == 1      # the big one, degraded
    comps = {c.rid: c for c in b.drain()}
    assert sorted(comps) == sorted([small, big])


# ---------------------------------------------------------------------------
# the full chaos sweep: every fault class under Poisson traffic
# ---------------------------------------------------------------------------

def test_chaos_sweep_zero_lost_bit_exact(tiny_setup):
    cfg, params = tiny_setup
    clock = FakeClock()
    faults = FaultPlan.chaos(seed=0)
    buckets = (BucketShape(2, 16), BucketShape(2, 24))
    eng = _engine(cfg, params, clock, faults=faults, threshold=2,
                  cooldown=0.05, buckets=buckets)
    rng = np.random.default_rng(0)
    arrivals = poisson_arrivals(80.0, 0.5, rng)
    specs = _request_specs(len(arrivals), cfg.vocab, 6, 4, rng)
    t0 = clock()
    rid_to_spec = {}
    rejected = 0
    i = 0
    while i < len(arrivals) or eng.depth():
        now = clock() - t0
        while i < len(arrivals) and arrivals[i] <= now:
            p, nt = specs[i]
            arrived = t0 + arrivals[i]
            try:
                rid = eng.submit(p, nt, submit_t=arrived,
                                 deadline=arrived + 2.0)
                rid_to_spec[rid] = (p, nt)
            except Backpressure:
                rejected += 1
            i += 1
        if eng.step():
            continue
        if i < len(arrivals):
            clock.advance(max(arrivals[i] - (clock() - t0), 1e-4))
        elif eng.depth():
            eng.step(force=True)

    # every admitted request reached exactly one terminal outcome
    assert set(eng.outcomes) == set(rid_to_spec)        # ZERO lost
    assert all(o["outcome"] in ("ok", "shed", "failed")
               for o in eng.outcomes.values())
    ok = [r for r, o in eng.outcomes.items() if o["outcome"] == "ok"]
    comps = {c.rid: c for c in eng.completions}
    assert sorted(ok) == sorted(comps)
    for rid in ok:
        assert len(comps[rid].tokens) == rid_to_spec[rid][1]
    assert len(ok) + rejected > 0 and len(ok) > 0
    # the injected schedule actually fired across classes
    fired = faults.counts()
    assert fired.get("compile_fail", 0) >= 2
    assert fired.get("kernel_loss", 0) >= 1
    assert fired.get("slow_wave", 0) >= 1
    assert eng.metrics.quarantines >= 1

    # quarantined buckets demonstrably recover: size a probe request
    # to each bucket's own shape and loop until the probe wave lands
    lo = 0
    for shape in eng.buckets:
        probe = (tuple(range(max(lo + 1, shape.s_max - 6))), 4)
        lo = shape.s_max
        for _ in range(50):
            if eng.bucket_health()[shape.key] == "healthy":
                break
            clock.advance(0.06)
            eng.step()                        # tick breakers -> probing
            rid = eng.submit(*probe)
            done = {c.rid: c for c in eng.drain()}
            if rid in done and done[rid].bucket_key == shape.key:
                break
        assert eng.bucket_health()[shape.key] == "healthy", shape.key
    assert eng.metrics.recoveries >= 1

    # completed tokens are bit-exact vs fault-free single-request runs
    # in the same bucket shape each completion actually used
    shapes = {st.bucket.key: st.bucket for st in eng._states.values()}
    fb_key = eng._states[FALLBACK_KEY].bucket.key \
        if FALLBACK_KEY in eng._states else None
    refs = {}
    for rid in sorted(ok)[:8]:
        c = comps[rid]
        if c.bucket_key not in refs:
            refs[c.bucket_key] = Engine(
                cfg, params, compute="sdv",
                buckets=(shapes[c.bucket_key],), clock=FakeClock(),
                plan_policy=("default" if c.bucket_key == fb_key
                             else None))
        ref = refs[c.bucket_key]
        p, nt = rid_to_spec[rid]
        ref_rid = ref.submit(p, nt)
        ref_comp = {r.rid: r for r in ref.drain()}[ref_rid]
        assert ref_comp.tokens == c.tokens, (rid, c.bucket_key)


def test_chaos_sweep_zero_lost_with_midwave_joins(tiny_setup):
    """The chaos schedule with mid-wave joins enabled: faults can kill
    a wave that holds joiners mid-prefill and mid-decode, and freed
    slots keep refilling between injections.  Every admitted request
    must still reach exactly one terminal outcome, and joins must
    actually occur (the sweep is vacuous otherwise)."""
    cfg, params = tiny_setup
    clock = FakeClock()
    faults = FaultPlan.chaos(seed=1)
    eng = _engine(cfg, params, clock, faults=faults, threshold=2,
                  cooldown=0.05, buckets=(BucketShape(2, 16),
                                          BucketShape(2, 24)),
                  midwave_joins=True, prefill_chunk=4)
    rng = np.random.default_rng(1)
    arrivals = poisson_arrivals(60.0, 0.4, rng)
    specs = _request_specs(len(arrivals), cfg.vocab, 6, 6, rng)
    t0 = clock()
    rid_to_spec = {}
    i = 0
    while i < len(arrivals) or eng.depth():
        now = clock() - t0
        while i < len(arrivals) and arrivals[i] <= now:
            p, nt = specs[i]
            arrived = t0 + arrivals[i]
            try:
                rid = eng.submit(p, nt, submit_t=arrived,
                                 deadline=arrived + 2.0)
                rid_to_spec[rid] = (p, nt)
            except Backpressure:
                pass
            i += 1
        if eng.step():
            continue
        if i < len(arrivals):
            clock.advance(max(arrivals[i] - (clock() - t0), 1e-4))
        elif eng.depth():
            eng.step(force=True)

    assert set(eng.outcomes) == set(rid_to_spec)        # ZERO lost
    assert all(o["outcome"] in ("ok", "shed", "failed")
               for o in eng.outcomes.values())
    ok = [r for r, o in eng.outcomes.items() if o["outcome"] == "ok"]
    comps = {c.rid: c for c in eng.completions}
    assert sorted(ok) == sorted(comps) and len(ok) > 0
    for rid in ok:
        assert len(comps[rid].tokens) == rid_to_spec[rid][1]
    # joins really happened under injection, and some joiners finished
    assert eng.metrics.midwave_joins > 0
    assert any(comps[r].midwave_join for r in ok)
    assert faults.counts().get("kernel_loss", 0) >= 1


def test_run_poisson_chaos_ledger(tiny_setup):
    """The loadgen-level chaos drive: retries with seeded backoff,
    malformed extras riding along, and a client-side ledger where
    every offered request lands in exactly one terminal outcome with
    zero lost."""
    cfg, params = tiny_setup
    clock = FakeClock()
    faults = FaultPlan.chaos(seed=1, classes=("kernel_loss", "malformed"))
    eng = _engine(cfg, params, clock, faults=faults, threshold=2,
                  cooldown=0.05, buckets=(BucketShape(2, 16),),
                  queue_budget=8)
    snap = run_poisson(eng, rate=60.0, duration_s=0.4, prompt_len=6,
                       new_tokens=4, rng=np.random.default_rng(1),
                       slo_s=2.0, retries=2, backoff_s=0.005,
                       faults=faults, sleep=clock.advance)
    counts = snap["client_outcomes"]
    assert snap["lost_requests"] == 0 and counts["lost"] == 0
    assert sum(counts.values()) == snap["offered_requests"]
    assert counts["ok"] > 0
    if faults.counts().get("malformed"):
        assert snap["malformed_submitted"] > 0
    json.loads(json.dumps(snap))              # BENCH_7-able payload
