"""The program's spans reduced with a device trace (``programspans``):
device time split between ``engine.prefill`` and ``engine.decode``,
idle time named by the innermost program span with the host traced and
without, and the engine's readings on hand-made traces with known
answers."""
import os

import pytest

import programspans as P
import tracereduce as T
from repro.serving.metrics import SpanRecord
from tracereduce import Event

BENCH = os.path.dirname(os.path.abspath(__file__))
DEV, HOST = "/device:TPU:0", "/host:CPU"


def dev(start, dur, name="%fusion.1 = bf16[16] fusion(%a)"):
    return Event(DEV, T.DEVICE_OP_LINE, name, start, dur)


def span(name, start, end, n=None, rid=None):
    return SpanRecord(name, start, end, None, rid, n)


def decode_trace():
    """Window 0..1000 ns; two steps.  Step 1 (0..480): schedule, a
    prefill of 2 chunks (device 60..200), a decode of 4 slots (device
    250..400), sample.  Step 2 (500..900): a decode only (device
    560..820).  Device ops outside both: 950..990 (in ``engine.submit``)."""
    events = [Event(HOST, "python", T.WINDOW_SPAN, 0, 1000),
              dev(60, 140), dev(250, 150), dev(560, 260), dev(950, 40)]
    program = P.program_events([
        span("program.window", 2, 998),
        span("engine.step", 0, 480),
        span("engine.schedule", 0, 20),
        span("engine.prefill", 30, 210, n=2),
        span("engine.decode", 220, 410, n=4),
        span("engine.sample", 410, 480),
        span("request.queue", 440, 700, rid=3),
        span("engine.step", 500, 900),
        span("engine.decode", 520, 830, n=4),
        span("engine.sample", 830, 900),
        span("engine.submit", 930, 1000),
        span("request.prefill", 700, None, rid=3),     # still open
    ])
    return events, program


def test_program_events_carry_counts_and_leave_open_spans_out():
    _, program = decode_trace()
    assert all(e.plane == P.PROGRAM_PLANE for e in program)
    assert "request.prefill" not in {e.name for e in program}
    pre, = [e for e in program if e.name == "engine.prefill"]
    assert (pre.start_ns, pre.dur_ns, P.span_n(pre)) == (30, 180, 2)
    q, = [e for e in program if e.name == "request.queue"]
    assert q.detail == "n=None rid=3" and P.span_n(q) == 0


def test_device_time_splits_between_prefill_and_decode():
    events, program = decode_trace()
    r = P.reduce(events, program)
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["busy_s"] == pytest.approx(590e-9)
    assert r["steps"] == 2 and r["decode_dispatches"] == 2
    assert r["prefill_chunks"] == 2
    # 140 ns of device time in the prefill span, over 2 chunks
    assert r["prefill_chunk_ms"] == pytest.approx(70e-6)
    # 150 + 260 ns in the two decode spans
    assert r["decode_dispatch_ms"] == pytest.approx(205e-6)
    # the op in engine.submit is neither: 550 of 590 ns explained
    assert r["covered_share"] == pytest.approx(550 / 590)
    # host time per step: 480 - 180 - 190 and 400 - 310
    assert r["engine_host_ms"] == pytest.approx((110 + 90) / 2 / 1e6)
    assert r["program_window_offset_us"] == pytest.approx([2e-3, -2e-3])


def test_idle_named_by_the_innermost_program_span():
    events, program = decode_trace()
    r = P.reduce(events, program)
    idle = dict(r["idle_by_span"])
    assert sum(idle.values()) == pytest.approx(r["idle_s"])
    assert r["idle_s"] == pytest.approx(410e-9)
    assert idle == pytest.approx({
        "engine.schedule": 20e-9,
        "engine.step": 10e-9 + 10e-9 + 20e-9,  # 20..30, 210..220, 500..520
        "engine.prefill": 30e-9 + 10e-9,       # 30..60, 200..210
        "engine.decode": 30e-9 + 10e-9 + 40e-9 + 10e-9,
        # the queue span is shorter than step 2 and overlaps sample 1,
        # but names only 480..500, where no step is open
        "engine.sample": 70e-9 + 70e-9,
        "request.queue": 20e-9,
        P.NO_SPAN: 30e-9,                      # 900..930
        "engine.submit": 20e-9 + 10e-9,        # 930..950, 990..1000
    })
    assert r["named_share"] == pytest.approx(1 - 30 / 410)


def test_idle_named_without_the_host_trace():
    """Host tracing off: the window is ``window_s`` from the first
    device op, and the frame spans name its gaps."""
    events = [dev(100, 300), dev(500, 300), dev(900, 100)]
    program = P.program_events([
        span("program.window", 90, 1200),
        span("frame.transfer_in", 90, 100),
        span("frame.forward", 100, 110),
        span("frame.transfer_out", 110, 450),
        span("frame.transfer_in", 450, 480),
        span("frame.forward", 480, 490),
        span("frame.transfer_out", 490, 850),
    ])
    r = P.reduce(events, program, window_s=1e-6)
    assert r["window_s"] == pytest.approx(1e-6)
    assert r["busy_s"] == pytest.approx(700e-9)
    # window 100..1100; gaps 400..500, 800..900, 1000..1100
    assert dict(r["idle_by_span"]) == pytest.approx({
        "frame.transfer_out": 50e-9 + 10e-9 + 50e-9,
        "frame.transfer_in": 30e-9,            # 450..480
        "frame.forward": 10e-9,                # 480..490
        P.NO_SPAN: 50e-9 + 100e-9,             # 850..900, 1000..1100
    })
    assert r["named_share"] == pytest.approx(0.5)
    assert r["first_op_after_program_window_us"] == pytest.approx(10e-3)
    assert "engine_host_ms" not in r


def test_frame_loop_is_the_one_the_runner_wraps():
    with open(os.path.join(BENCH, "drivers", "frames.py")) as f:
        assert f.read().count(P.FRAME_LOOP) == 1



def _harness():
    """Just what the runner patches: ``load_module`` and ``Cell``."""
    import contextlib
    import types

    class Cell:
        @contextlib.contextmanager
        def window(self, out, host_spans=True):
            out["opened"] = True
            yield

    return types.SimpleNamespace(Cell=Cell,
                                 load_module=lambda path, name: name)


@pytest.mark.parametrize("listener", [True, False])
def test_runner_records_around_the_window(listener):
    import jax
    import jax.numpy as jnp

    from repro.serving.metrics import SPANS
    harness, found, out = _harness(), {}, {}
    P._record_around_window(harness, T, "rec", listener, found)
    with harness.Cell().window(out):
        assert SPANS.on
        with SPANS.span("engine.step"):
            jax.block_until_ready(jax.jit(lambda x: x * 5 - 2)(
                jnp.arange(11, dtype=jnp.int32)))
    assert not SPANS.on and out == {"opened": True}
    names = [r.name for r in SPANS.records()]
    assert names[:2] == ["program.window", "engine.step"]
    # --no-compile-listener: recorded, but no compile span
    assert ("jax.compile" in names) is listener
    assert found["recorded"] == len(names) and found["dropped"] == 0
    assert found["step_ms_max"] > 0


def test_runner_loads_the_frame_loop_in_spans():
    from repro.serving.metrics import SPANS
    harness = _harness()
    P._frames_with_spans(harness)
    assert harness.load_module("drivers/decode.py", "decode") == "decode"
    mod = harness.load_module(os.path.join(BENCH, "drivers", "frames.py"),
                              "frames_in_spans")
    assert mod.SPANS is SPANS and callable(mod.run)
