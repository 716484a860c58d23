"""The serving program's own spans on a device trace's clock, and what
they read per layer.

The program records its spans in ``repro.serving.metrics.SPANS``
(``engine.step`` and its children, ``request.*``, ``jax.compile``) on
``perf_counter_ns``, with one ``(time_ns, perf_counter_ns)`` anchor.
A profiler trace stamps its events relative to the ``profile_start_time``
stat of its ``Task Environment`` plane, in wall-clock ns, so
``SPANS.timeline(profile_start_time)`` puts the spans on the trace's own
clock, with or without the host tracer.  This module turns them into
trace ``Event``s on a ``/host:program`` plane and reduces them with the
device ops: the idle time each span holds, the engine's host time per
step, and the device time per decode dispatch and per prefill chunk.

No cell reads it yet: ``bench/run.py`` does not import it, and the
per-cell readers do not see the spans.  Run as a script, it runs one
cell as ``bench/run.py`` does with the recorder on around the window,
and prints the cell's result line with the reduction under ``"spans"``:

    python3 bench/programspans.py --workload <cell> --seed <n> \\
        --seconds <s> --mode off|rec|traced [--no-compile-listener]

``off`` leaves the recorder off (the same process path, for pairs);
``rec`` turns it on with the profiler off (its cost); ``traced`` turns
it on under ``--trace 1`` and maps the spans onto the trace.
``drivers/frames.py`` opens no spans of its own, so the runner loads
it with its loop wrapped in ``frame.transfer_in``, ``frame.forward``
and ``frame.transfer_out``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import bisect  # noqa: E402
import contextlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from typing import Dict, List, Optional, Sequence, Tuple  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from tracereduce import (WINDOW_SPAN, Event, _device_ops,  # noqa: E402
                         _union)

#: the host plane the program's spans are put on
PROGRAM_PLANE = "/host:program"
#: the span the runner opens inside ``bench.window``, to check the clock
PROGRAM_WINDOW = "program.window"
#: spans that name idle time
PREFIXES = ("engine.", "frame.", "request.", "jax.")
#: what idle time inside no program span is called
NO_SPAN = "(no program span)"

Interval = Tuple[float, float]


def profile_start_time(xplane_path: str) -> int:
    """The wall-clock ns a trace's event times are relative to."""
    from jax.profiler import ProfileData
    for plane in ProfileData.from_file(xplane_path).planes:
        if plane.name == "Task Environment":
            return int(dict(plane.stats)["profile_start_time"])
    raise RuntimeError(f"no Task Environment plane in {xplane_path}")


def program_events(records) -> List[Event]:
    """``SpanRecord``s already on the trace's clock (``timeline``) as
    trace events; a span still open (a request waiting when recording
    stopped) is left out.  ``detail`` carries ``n=<n> rid=<rid>``."""
    return [Event(PROGRAM_PLANE, "spans", r.name, float(r.start_ns),
                  float(r.end_ns - r.start_ns), f"n={r.n} rid={r.rid}")
            for r in records if r.end_ns is not None]


def span_n(e: Event) -> int:
    """The count a program span recorded at its boundary (0 if none)."""
    n = e.detail.split()[0][2:]
    return int(n) if n != "None" else 0


def window(events: Sequence[Event], window_s: Optional[float]
           ) -> Interval:
    """The measured window: ``bench.window`` where the host was traced,
    else ``window_s`` from the first device op (as ``summarize``)."""
    if window_s is None:
        w, = [e for e in events if e.name == WINDOW_SPAN
              and not e.plane.startswith("/device:")]
        return w.start_ns, w.end_ns
    w0 = min(e.start_ns for e in _device_ops(events))
    return w0, w0 + window_s * 1e9


def covered(busy: Sequence[Interval], s: float, e: float) -> float:
    """How much of the sorted, disjoint ``busy`` lies inside [s, e]."""
    return sum(max(0.0, min(b, e) - max(a, s)) for a, b in busy
               if b > s and a < e)


def idle_by_span(busy: Sequence[Interval], spans: Sequence[Event],
                 w0: float, w1: float) -> Dict[str, float]:
    """Idle seconds of [w0, w1] by the innermost span open over each
    piece: a gap that spans several is split at their edges, so each
    piece takes its own name.  Spans nest by time, so the innermost is
    the shortest; a request's spans nest in no step, so they name only
    what no other span holds (a request waiting between steps)."""
    spans = sorted((e for e in spans if e.end_ns > w0 and e.start_ns < w1),
                   key=lambda e: e.start_ns)
    starts = [e.start_ns for e in spans]
    edges = [w0] + [x for a, b in busy for x in (a, b)] + [w1]
    out: Dict[str, float] = {}
    for a, b in zip(edges[::2], edges[1::2]):
        if b <= a:
            continue
        near = [e for e in spans[:bisect.bisect_left(starts, b)]
                if e.end_ns > a]
        cuts = sorted({a, b} | {x for e in near
                                for x in (e.start_ns, e.end_ns) if a < x < b})
        for x, y in zip(cuts, cuts[1:]):
            m = (x + y) / 2
            inside = [e for e in near if e.start_ns <= m <= e.end_ns]
            name = min(inside, key=lambda e: (
                e.name.startswith("request."), e.dur_ns)).name \
                if inside else NO_SPAN
            out[name] = out.get(name, 0.0) + (y - x) / 1e9
    return out


def engine_readings(spans: Sequence[Event], busy: Sequence[Interval],
                    w0: float, w1: float) -> Dict[str, Optional[float]]:
    """The engine's per-layer numbers from the window's spans.

    ``engine_host_ms``: the mean over ``engine.step`` spans that open
    in the window of the step's time less what its ``engine.prefill``
    and ``engine.decode`` spans cover.  ``decode_dispatch_ms``: device
    busy time inside ``engine.decode`` spans over their count;
    ``prefill_chunk_ms``: inside ``engine.prefill`` spans over the
    chunks they dispatched (``None`` without one).  ``covered_share``:
    what those two explain of the window's busy time."""
    inw = [e for e in spans if e.end_ns > w0 and e.start_ns < w1]
    steps = [e for e in inw if e.name == "engine.step"
             and w0 <= e.start_ns < w1]
    if not steps:
        return {}
    work = sorted((e for e in inw
                   if e.name in ("engine.prefill", "engine.decode")),
                  key=lambda e: e.start_ns)
    starts = [e.start_ns for e in work]
    host_ms = []
    for st in steps:
        lo = bisect.bisect_left(starts, st.start_ns)
        hi = bisect.bisect_right(starts, st.end_ns)
        inner = sum(e.dur_ns for e in work[lo:hi] if e.end_ns <= st.end_ns)
        host_ms.append((st.dur_ns - inner) / 1e6)
    clipped = [(max(a, w0), min(b, w1)) for a, b in busy]
    busy_ns = sum(b - a for a, b in clipped if b > a)

    def busy_in(name):
        sel = [e for e in work if e.name == name]
        return sel, sum(covered(busy, e.start_ns, e.end_ns) for e in sel)

    dec, dec_ns = busy_in("engine.decode")
    pre, pre_ns = busy_in("engine.prefill")
    chunks = sum(span_n(e) for e in pre)
    return {
        "engine_host_ms": sum(host_ms) / len(host_ms),
        "steps": len(steps),
        "decode_dispatches": len(dec),
        "prefill_chunks": chunks,
        "decode_dispatch_ms": dec_ns / len(dec) / 1e6 if dec else None,
        "prefill_chunk_ms": pre_ns / chunks / 1e6 if chunks else None,
        "covered_share": (dec_ns + pre_ns) / busy_ns if busy_ns else None,
    }


def reduce(events: Sequence[Event], program: Sequence[Event],
           window_s: Optional[float] = None) -> Dict:
    """One traced run's device events and the program's spans (both on
    the trace's clock) reduced to named idle time, the engine's
    readings, and where ``program.window`` lies against the window."""
    w0, w1 = window(events, window_s)
    ops = [(max(e.start_ns, w0), min(e.end_ns, w1))
           for e in _device_ops(events) if e.end_ns > w0 and e.start_ns < w1]
    busy = _union(ops)
    spans = [e for e in program if e.name.startswith(PREFIXES)]
    idle = idle_by_span(busy, spans, w0, w1)
    idle_s = sum(idle.values())
    out = {"window_s": (w1 - w0) / 1e9,
           "busy_s": sum(b - a for a, b in busy) / 1e9,
           "idle_s": idle_s,
           "idle_by_span": sorted(idle.items(), key=lambda kv: -kv[1]),
           "named_share": 1 - idle.get(NO_SPAN, 0.0) / idle_s
           if idle_s else None}
    pw = [e for e in program if e.name == PROGRAM_WINDOW]
    if len(pw) == 1 and window_s is None:
        out["program_window_offset_us"] = [(pw[0].start_ns - w0) / 1e3,
                                           (pw[0].end_ns - w1) / 1e3]
    elif len(pw) == 1:
        out["first_op_after_program_window_us"] = (w0 - pw[0].start_ns) / 1e3
    out.update(engine_readings(spans, busy, w0, w1))
    return out


# ---------------------------------------------------------------------------
# the runner
# ---------------------------------------------------------------------------

FRAME_LOOP = """            x = jax.device_put(pool[len(outs) % len(pool)])
            y = fwd(convs, head, x)
            outs.append(np.asarray(y)[0])
"""
FRAME_LOOP_SPANS = """            with SPANS.span("frame.transfer_in"):
                x = jax.device_put(pool[len(outs) % len(pool)])
            with SPANS.span("frame.forward"):
                y = fwd(convs, head, x)
            with SPANS.span("frame.transfer_out"):
                outs.append(np.asarray(y)[0])
"""


def _frames_with_spans(harness) -> None:
    """Load ``drivers/frames.py`` with its window loop in frame spans."""
    load = harness.load_module

    def load_module(path, name):
        if not path.endswith(os.path.join("drivers", "frames.py")):
            return load(path, name)
        import types
        with open(path) as f:
            src = f.read()
        if src.count(FRAME_LOOP) != 1:
            raise RuntimeError("the frame loop changed: update FRAME_LOOP")
        mod = types.ModuleType(name)
        mod.__file__ = path
        mod.SPANS = _recorder()
        exec(compile(src.replace(FRAME_LOOP, FRAME_LOOP_SPANS), path,
                     "exec"), mod.__dict__)
        return mod
    harness.load_module = load_module


def _recorder():
    from repro.serving.metrics import SPANS
    return SPANS


def _record_around_window(harness, tracereduce, mode: str,
                          compile_listener: bool, found: Dict) -> None:
    """Turn the recorder on around each window, with ``program.window``
    inside ``bench.window``; in ``traced`` mode reduce the spans with
    the trace before its directory is removed."""
    import jax
    spans = _recorder()
    window_fn = harness.Cell.window
    trace_window = tracereduce.trace_window

    @contextlib.contextmanager
    def recorded(self, out, host_spans=True):
        spans.start()
        if not compile_listener:
            jax.monitoring.unregister_event_duration_listener(
                spans._on_duration)
        try:
            with window_fn(self, out, host_spans):
                with spans.span(PROGRAM_WINDOW):
                    yield
        finally:
            if not compile_listener:
                jax.monitoring.register_event_duration_secs_listener(
                    spans._on_duration)
            found["recorded"] = len(spans.stop())
            found["dropped"] = spans.dropped
            steps = sorted(r.end_ns - r.start_ns for r in spans.records()
                           if r.name == "engine.step"
                           and r.end_ns is not None)
            if steps:
                found["step_ms_max"] = steps[-1] / 1e6

    def traced(log_dir, window_s=None):
        path = tracereduce.find_xplane(log_dir)
        events = tracereduce.events_from_xplane(path)
        program = program_events(spans.timeline(profile_start_time(path)))
        found.update(reduce(events, program, window_s))
        return trace_window(log_dir, window_s)

    if mode != "off":
        harness.Cell.window = recorded
    if mode == "traced":
        tracereduce.trace_window = traced


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--mode", choices=("off", "rec", "traced"),
                    required=True)
    ap.add_argument("--no-compile-listener", action="store_true",
                    help="record, but register no jax.monitoring listener")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    import harness
    import tracereduce
    found: Dict = {}
    _frames_with_spans(harness)
    _record_around_window(harness, tracereduce, args.mode,
                          not args.no_compile_listener, found)
    spec = harness.load_json(os.path.join(ROOT, "BENCHMARK.json"))
    chips = {w["name"]: w["chips"] for w in spec["workloads"]}
    try:
        device, peak = harness.open_chip(ROOT, chips[args.workload])
    except harness.HarnessError as e:
        print(f"programspans: {e}; no result", file=sys.stderr)
        return 2
    result = harness.run_cell(ROOT, args.workload, args.seed, args.seconds,
                              args.mode == "traced", peak=peak,
                              t_start=T_START, device=device)
    result["spans"] = found
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
