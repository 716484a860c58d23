"""The trace reduction: busy time as the union of device ops inside the
window, kernel time by name, idle gaps by the host span around them.
Checked on a hand-made trace with known answers and on a short trace
recorded on a TPU v5e (``testdata/``)."""
import glob
import os

import pytest

import tracereduce as T
from tracereduce import Event

BENCH = os.path.dirname(os.path.abspath(__file__))
DEV, HOST = "/device:TPU:0", "/host:CPU"


def dev(name, start, dur, detail=""):
    return Event(DEV, T.DEVICE_OP_LINE, name, start, dur, detail)


def host(name, start, dur):
    return Event(HOST, "python", name, start, dur)


def hand_trace():
    # window 1000..2000 ns; ops overlap, one starts before the window
    return [
        host(T.WINDOW_SPAN, 1000, 1000),
        host("engine.step", 1000, 600),
        host("engine.submit", 1700, 200),
        dev("%while.7 = (s32[]) while(%tuple)", 1400, 250),  # holds ops
        dev("%sdv_matmul.1 = s32[3,16,342] custom-call(%a, %b)", 900, 300),
        dev("%fusion.2 = bf16[16] fusion(%c)", 1150, 100),
        dev("%sdv_matvec.3 = s32[3,8,342] custom-call(%d, %e)", 1400, 200),
        dev("%fusion.4 = bf16[16] fusion(%f)", 1500, 50),
        Event(DEV, "XLA Modules", "jit__lambda", 900, 1100),   # not an op
        dev("fusion.5", 2100, 100),                            # outside
    ]


def test_hand_trace():
    s = T.summarize(hand_trace())
    assert s.window_s == pytest.approx(1e-6)
    # busy: [1000,1250) + [1400,1650) = 500 ns
    assert s.busy_s == pytest.approx(500e-9)
    assert s.idle_share() == pytest.approx(0.5)
    # op times are clipped to the window: 200 + 200 ns
    assert s.op_seconds(r"sdv_mat(mul|vec)") == pytest.approx(400e-9)
    assert s.op_seconds(r"fusion") == pytest.approx(150e-9)
    # gaps: [1250,1400) inside engine.step, [1650,2000) mid 1825 inside
    # engine.submit
    assert dict(s.top_gaps()) == pytest.approx(
        {"engine.step": 150e-9, "engine.submit": 350e-9})
    # by instruction name, the loop that holds ops left out
    assert dict(s.top_ops()) == pytest.approx(
        {"sdv_matmul": 200e-9, "sdv_matvec": 200e-9, "fusion": 150e-9})


def test_window_without_host_trace():
    # host tracing off: the window opens with the first device op and
    # lasts as long as the host clock says; every gap is unnamed
    ops = [dev("%fusion.1 = f32[8] fusion(%a)", 100, 200),
           dev("%bseg_conv2d.2 = s32[8] custom-call(%b)", 250, 150),
           dev("%copy.3 = s32[8] copy(%c)", 600, 100)]
    s = T.summarize(ops, window_s=1e-6)
    assert s.window_s == pytest.approx(1e-6)
    assert s.busy_s == pytest.approx(400e-9)
    assert s.op_seconds(r"bseg_conv2d") == pytest.approx(150e-9)
    assert dict(s.top_gaps()) == pytest.approx({T.UNTRACED_HOST: 600e-9})
    assert T.summarize([], window_s=1.0).devices == 0


def test_needs_one_window():
    with pytest.raises(RuntimeError):
        T.summarize([dev("fusion.1", 0, 10)])


RECORDED = sorted(glob.glob(os.path.join(BENCH, "testdata", "events_*.json")))

#: read off these recorded traces by this reduction when they were
#: recorded (one TPU v5e): busy seconds, and the kernel's seconds
PINNED = {
    "events_granite8b-sdv.decode.json": (0.29880005, r"sdv_mat", 0.28311447),
    "events_granite8b-mem.decode.json": (0.092068919, r"sdv_mat", 0.0),
    "events_ultranet.stream.json": (0.094003128, r"bseg_conv2d", 0.093215991),
}


@pytest.mark.parametrize("path", RECORDED,
                         ids=[os.path.basename(p) for p in RECORDED])
def test_recorded_trace(path):
    events = T.load_events(path)
    s = T.summarize(events)
    w = [e for e in events if e.name == T.WINDOW_SPAN][0]
    ops = [e for e in events if e.plane.startswith("/device:TPU")
           and e.line == T.DEVICE_OP_LINE and e.dur_ns > 0
           and e.end_ns > w.start_ns and e.start_ns < w.end_ns]
    assert ops and s.devices == 1
    # busy never exceeds the window nor the plain sum of op times
    assert 0 < s.busy_s <= s.window_s
    assert s.busy_s <= sum(min(e.end_ns, w.end_ns)
                           - max(e.start_ns, w.start_ns)
                           for e in ops) / 1e9 + 1e-12
    # kernel time by name is the plain sum of the matching events
    for pattern in (r"sdv_mat(mul|vec)", r"bseg_conv2d"):
        want = sum(min(e.end_ns, w.end_ns) - max(e.start_ns, w.start_ns)
                   for e in ops if e.matches(pattern)) / 1e9
        assert s.op_seconds(pattern) == pytest.approx(want)
    assert sum(v for _, v in s.gaps) == pytest.approx(
        s.window_s - s.busy_s, rel=1e-9, abs=1e-12)
    busy, pattern, kernel = PINNED[os.path.basename(path)]
    assert s.busy_s == pytest.approx(busy, rel=1e-6)
    assert s.op_seconds(pattern) == pytest.approx(kernel, rel=1e-6, abs=1e-12)
