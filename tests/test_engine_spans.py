"""The serving program's span recorder (``metrics.Spans``) and the
engine counters it sits beside: spans nest as the engine's step does,
cost nothing while off, carry request ids and boundary counts, and sit
on the clock of a profiler trace; occupancy is true mid-wave."""
import glob
import os
import time

import jax
import jax.numpy as jnp
import pytest

from repro.serving import BucketShape, FaultPlan
from repro.serving import metrics
from repro.serving.engine import Engine
from repro.serving.metrics import COMPILE_EVENT, SPANS, EngineMetrics

STEP_CHILDREN = ("engine.schedule", "engine.join", "engine.prefill",
                 "engine.decode", "engine.sample")


@pytest.fixture(scope="module")
def tiny_setup():
    from repro.configs.registry import get_arch
    from repro.models import Rules, init_params, values
    cfg = get_arch("tinyllama-1.1b").reduced()
    rules = Rules(tp=None, fsdp=None, ep=None, batch=())
    return cfg, values(init_params(cfg, rules, jax.random.PRNGKey(0)))


@pytest.fixture
def recording():
    """The engine's recorder, on for the test and off after it."""
    SPANS.start()
    try:
        yield SPANS
    finally:
        SPANS.stop()


def _engine(tiny_setup, **kw):
    cfg, params = tiny_setup
    kw.setdefault("buckets", (BucketShape(2, 24),))
    return Engine(cfg, params, compute="sdv", prefill_chunk=4, **kw)


class _Counted:
    def __init__(self, fn):
        self.fn, self.calls = fn, 0

    def __call__(self, *a, **k):
        self.calls += 1
        return self.fn(*a, **k)


def test_engine_spans_nest_per_step(tiny_setup, recording):
    spans = recording
    eng = _engine(tiny_setup)
    eng.warmup(BucketShape(2, 24), inject=False)
    eng._pre = pre = _Counted(eng._pre)
    prompts = [((1, 2, 3, 4, 5, 6, 7, 8, 9), 6), ((10, 11), 4),
               ((12, 13, 14, 15, 16, 17), 2)]
    spans.start()                       # forget the warm-up's spans
    rids = [eng.submit(p, n) for p, n in prompts]
    steps = 0
    while eng.depth():
        eng.step(force=True)
        steps += 1
    recs = spans.stop()
    assert spans.dropped == 0 and all(r.end_ns is not None for r in recs)

    top = [i for i, r in enumerate(recs) if r.name == "engine.step"]
    assert len(top) == steps and all(recs[i].parent is None for i in top)
    assert [r.name for r in recs if r.name == "engine.submit"] \
        == ["engine.submit"] * len(prompts)
    for i in top:
        kids = [r for r in recs if r.parent == i]
        names = [r.name for r in kids]
        assert names[0] == "engine.schedule"
        # one wave iteration per step (wave_quantum 1): its spans in
        # the order the iteration runs them
        order = [STEP_CHILDREN.index(n) for n in names]
        assert order == sorted(order) and len(set(order)) == len(order)
        for k in kids:
            assert recs[i].start_ns <= k.start_ns <= k.end_ns \
                <= recs[i].end_ns
    # the prefill span counts the chunks it dispatched
    assert sum(r.n for r in recs if r.name == "engine.prefill") \
        == pre.calls == eng.metrics.prefill_chunks > 0
    dec = [r for r in recs if r.name == "engine.decode"]
    assert dec and all(1 <= r.n <= 2 for r in dec)
    assert sum(r.n for r in recs if r.name == "engine.join") == 1
    # a request's queue wait and prefill carry its id, one each, in
    # order, and lie outside any step's nesting
    for rid in rids:
        mine = [r for r in recs if r.rid == rid]
        assert [r.name for r in mine] == ["request.queue",
                                          "request.prefill"]
        q, p = mine
        assert q.parent is None and p.parent is None
        assert q.end_ns <= p.start_ns < p.end_ns


def test_recorder_off_records_nothing(tiny_setup):
    eng = _engine(tiny_setup)
    assert not SPANS.on
    n0 = len(SPANS.records())
    eng.submit((1, 2, 3), 2)
    eng.drain()
    assert len(SPANS.records()) == n0
    # off, a span is one shared object that reads no clock
    off = metrics.Spans()
    assert off.span("a") is off.span("b", rid=1, n=2)
    with off.span("a") as s:
        s.n = 3
    off.begin("request.queue", 1)
    off.end("request.queue", 1)
    assert off.records() == [] and off.timeline() == []


def test_recorder_cap_counts_what_it_drops(monkeypatch, recording):
    monkeypatch.setattr(metrics, "SPAN_CAP", 3)
    spans = recording
    with spans.span("outer"):
        for _ in range(4):
            with spans.span("inner", n=1):
                pass
    recs = spans.stop()
    assert [r.name for r in recs] == ["outer", "inner", "inner"]
    assert spans.dropped == 2
    assert recs[1].parent == recs[2].parent == 0
    # starting again forgets the old spans and takes a new anchor
    a0 = spans.anchor
    spans.start()
    assert spans.records() == [] and spans.dropped == 0
    assert spans.anchor != a0


def test_request_span_opens_once(recording):
    """A request span already open stays as it is when begun again,
    and an end with nothing open records nothing."""
    spans = recording
    spans.begin("request.queue", 7)
    spans.begin("request.queue", 7)
    spans.end("request.queue", 7)
    spans.end("request.queue", 7)
    spans.end("request.prefill", 8)
    recs = spans.records()
    assert [(r.name, r.rid) for r in recs] == [("request.queue", 7)]
    assert recs[0].end_ns is not None


def test_request_spans_close_at_every_outcome(tiny_setup, recording):
    """No request span is left open once every request has its
    outcome: one shed in the queue, and waves that lose their kernel
    route every time, so their requests go back to a queue and end on
    the fallback path."""

    class Clock:
        t = 0.0

        def __call__(self):
            return self.t

    clock = Clock()
    eng = _engine(tiny_setup, buckets=(BucketShape(2, 16),), clock=clock,
                  faults=FaultPlan(seed=0, kernel_loss_p=1.0),
                  breaker_threshold=2)
    shed = eng.submit((1, 2, 3), 2, deadline=1.0)
    clock.t = 2.0
    eng.step()
    assert eng.outcomes[shed]["outcome"] == "shed"
    rids = [eng.submit((1, 2, 3), 3), eng.submit((4, 5, 6, 7), 2)]
    eng.drain()
    assert all(eng.outcomes[r]["outcome"] == "ok" for r in rids)
    assert eng.metrics.fallback_waves == len(rids)
    recs = recording.stop()
    assert recording._pending == {}
    req = [r for r in recs if r.name.startswith("request.")]
    assert req and all(r.end_ns is not None for r in req)
    assert [r.name for r in req if r.rid == shed] == ["request.queue"]
    for rid in rids:
        names = [r.name for r in req if r.rid == rid]
        # each failed wave sends the request back to wait again
        assert names[:2] == ["request.queue", "request.prefill"]
        assert len(names) > 2 and names == \
            ["request.queue", "request.prefill"] * (len(names) // 2)
        mine = [r for r in req if r.rid == rid]
        assert all(a.end_ns <= b.start_ns for a, b in zip(mine, mine[1:]))


def test_compile_inside_a_span_is_a_span(recording):
    spans = recording
    with spans.span("engine.decode"):
        jax.block_until_ready(jax.jit(lambda x: x * 3 + 1)(
            jnp.arange(7, dtype=jnp.int32)))
    spans._on_duration(COMPILE_EVENT, 1.0)      # no span open: ignored
    recs = spans.stop()
    comp = [r for r in recs if r.name == "jax.compile"]
    assert comp and all(r.parent == 0 for r in comp)
    assert all(r.start_ns < r.end_ns for r in comp)
    # stopped, the listener is gone
    jax.block_until_ready(jax.jit(lambda x: x - 5)(jnp.arange(3)))
    assert len(spans.records()) == len(recs)


def test_timeline_shares_the_profiler_clock(tmp_path):
    """A program span and a ``TraceAnnotation`` over the same interval,
    the span mapped through the trace's ``profile_start_time``, agree
    within a millisecond (CPU trace).  The span opens just outside the
    annotation, so a thread descheduled between the two can only widen
    it: each span holds its annotation to within 1 ms at both ends (a
    mapping off by more fails one end), and the closest pair agrees to
    1 ms at both."""
    from jax.profiler import ProfileData
    spans = SPANS
    x = jnp.ones((64, 64), jnp.float32)
    jax.block_until_ready(x @ x)
    spans.start()
    jax.profiler.start_trace(str(tmp_path))
    try:
        for i in range(3):
            with spans.span(f"probe{i}"):
                with jax.profiler.TraceAnnotation(f"probe{i}"):
                    jax.block_until_ready(x @ x)
                    time.sleep(0.005)
    finally:
        jax.profiler.stop_trace()
        spans.stop()
    path, = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                      recursive=True)
    planes = list(ProfileData.from_file(path).planes)
    env, = [p for p in planes if p.name == "Task Environment"]
    origin = int(dict(env.stats)["profile_start_time"])
    traced = {e.name: (e.start_ns, e.start_ns + e.duration_ns)
              for p in planes if not p.name.startswith("/device:")
              for line in p.lines for e in line.events
              if e.name.startswith("probe")}
    mine = {r.name: (r.start_ns, r.end_ns) for r in spans.timeline(origin)}
    assert sorted(traced) == sorted(mine) == ["probe0", "probe1", "probe2"]
    for name, (s, e) in traced.items():
        assert mine[name][0] < s + 1e6 and mine[name][1] > e - 1e6, name
    assert min(max(abs(mine[k][0] - s), abs(mine[k][1] - e))
               for k, (s, e) in traced.items()) < 1e6


def test_occupancy_counts_a_wave_that_never_ends(tiny_setup):
    """Every iteration adds to occupancy, so a continuous wave that
    has not ended (joins keep it alive) reports it."""
    eng = _engine(tiny_setup)
    eng.submit((1, 2, 3, 4, 5), 6)
    eng.submit((6, 7), 6)
    for _ in range(3):
        eng.step(force=True)
    assert eng.busy()
    snap = eng.metrics.snapshot()
    assert snap["waves"]["count"] == 0
    assert snap["waves"]["slot_steps"] == 3 * 2
    assert snap["waves"]["busy_slot_steps"] == 3 * 2
    assert snap["waves"]["occupancy"] == 1.0
    # iteration 1 prefills both prompts (one chunk each), 2 and 3
    # decode both
    assert eng.metrics.prefill_chunks == 2
    assert eng.metrics.decode_launches == 2
    eng.drain()
    done = eng.metrics.snapshot()["waves"]
    assert done["count"] == 1 and 0 < done["occupancy"] <= 1.0
    assert done["slot_steps"] == 2 * done["steps"]


def test_queue_depth_keeps_running_totals():
    m = EngineMetrics(clock=lambda: 0.0)
    for d in (3, 0, 5, 2):
        m.sample_depth(d)
    assert (m.depth_samples, m.depth_sum, m.depth_max) == (4, 10, 5)
    assert m.snapshot()["queue_depth"] == {"mean": 2.5, "max": 5}
    assert EngineMetrics(clock=lambda: 0.0).snapshot()["queue_depth"] \
        == {"mean": 0.0, "max": 0}
    assert not any(isinstance(v, list) and k.startswith("depth")
                   for k, v in vars(m).items())
    assert m.snapshot()["waves"]["occupancy"] == 0.0
