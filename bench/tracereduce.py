"""From a profiler trace to the numbers the per-layer readers take.

Two steps, kept apart so that the second can be tested on a small
recorded trace: ``events_from_xplane`` flattens the profiler's
``.xplane.pb`` into plain event records, and ``summarize`` reduces
records to the traced window's busy time, the device time of each op,
and the idle gaps with what the host was doing in each.

Busy time is the union of the intervals in which an operation ran on a
device, clipped to the window the benchmark marks with its own host
span (``WINDOW_SPAN``), averaged over the devices used.
"""
from __future__ import annotations

import dataclasses
import glob
import json
import os
import re
from collections import defaultdict
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

#: the host span the harness puts around the measured window
WINDOW_SPAN = "bench.window"
#: the device line whose events are single operations
DEVICE_OP_LINE = "XLA Ops"
#: stats joined into an event's ``detail`` (op and module names, the
#: name stack of the JAX program that emitted it)
DETAIL_STATS = ("hlo_op", "hlo_module", "long_name", "tf_op", "name",
                "source", "kernel_details")


@dataclasses.dataclass(frozen=True)
class Event:
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float
    detail: str = ""

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns

    def matches(self, pattern: str) -> bool:
        return re.search(pattern, f"{self.name} {self.detail}") is not None


def events_from_xplane(path: str) -> List[Event]:
    """Every event of every plane in one ``.xplane.pb`` file."""
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                stats = dict(e.stats)
                detail = " ".join(str(stats[k]) for k in DETAIL_STATS
                                  if k in stats)
                out.append(Event(plane.name, line.name, e.name,
                                 float(e.start_ns), float(e.duration_ns),
                                 detail))
    return out


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, found "
                           f"{len(paths)}")
    return paths[0]


def save_events(events: Sequence[Event], path: str) -> None:
    with open(path, "w") as f:
        json.dump([dataclasses.astuple(e) for e in events], f)


def load_events(path: str) -> List[Event]:
    with open(path) as f:
        return [Event(*row) for row in json.load(f)]


def _union(intervals: Iterable[Tuple[float, float]]
           ) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


@dataclasses.dataclass
class Summary:
    window_s: float
    busy_s: float                   # averaged over the devices
    devices: int
    ops: List[Event]                # device ops inside the window
    gaps: List[Tuple[str, float]]   # (host span, idle seconds) per gap

    def op_seconds(self, pattern: str) -> float:
        """Device seconds of the ops whose name or detail matches, per
        device (``ops`` are clipped to the window)."""
        return sum(e.dur_ns for e in self.ops if e.matches(pattern)) \
            / 1e9 / max(self.devices, 1)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def top_ops(self, n: int = 10) -> List[List]:
        """The ops that took most device time, by ``op_label``; a loop
        or call op that holds other ops is left out (its body's ops
        are counted)."""
        by: Dict[str, float] = defaultdict(float)
        for e in self.ops:
            label = op_label(e)
            if label not in CONTAINERS:
                by[label] += e.dur_ns / 1e9 / max(self.devices, 1)
        return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])
                [:n]]

    def top_gaps(self, n: int = 10) -> List[List]:
        by: Dict[str, float] = defaultdict(float)
        for name, s in self.gaps:
            by[name] += s
        return [[k, v] for k, v in sorted(by.items(), key=lambda kv: -kv[1])
                [:n]]


#: ops whose span holds other ops of the same line
CONTAINERS = ("while", "conditional", "call")


def op_label(e: Event) -> str:
    """A device op's HLO instruction name without its number: on the
    TPU an op event is named by its instruction (``%sdv_matmul.48 =
    s32[...] custom-call(...)``), so this gives ``sdv_matmul``,
    ``fusion``, ``while``."""
    m = re.match(r"%?([\w\-]+?)(\.\d+)*(\s|$)", e.name)
    return m.group(1) if m else e.name[:60]


#: the name a gap takes where the host was not traced
UNTRACED_HOST = "(host not traced)"


def _device_ops(events: Sequence[Event]) -> List[Event]:
    return [e for e in events if e.plane.startswith("/device:TPU")
            and e.line == DEVICE_OP_LINE and e.dur_ns > 0]


def summarize(events: Sequence[Event], *,
              span_prefixes: Sequence[str] = ("engine.", "bench."),
              window_s: Optional[float] = None) -> Summary:
    """Reduce one traced run's events to its window's device numbers.

    The window is the benchmark's ``WINDOW_SPAN``; where the host was
    not traced, ``window_s`` gives its length by the host clock, the
    trace holds nothing but the window's device ops, and the window is
    taken to open with the first of them."""
    if window_s is None:
        windows = [e for e in events if e.name == WINDOW_SPAN
                   and not e.plane.startswith("/device:")]
        if len(windows) != 1:
            raise RuntimeError(f"expected one {WINDOW_SPAN!r} span, "
                               f"found {len(windows)}")
        w0, w1 = windows[0].start_ns, windows[0].end_ns
    else:
        ops = _device_ops(events)
        if not ops:
            return Summary(window_s=window_s, busy_s=0.0, devices=0,
                           ops=[], gaps=[])
        w0 = min(e.start_ns for e in ops)
        w1 = w0 + window_s * 1e9
    dev = [dataclasses.replace(e, start_ns=max(e.start_ns, w0),
                               dur_ns=min(e.end_ns, w1) - max(e.start_ns, w0))
           for e in _device_ops(events) if e.end_ns > w0 and e.start_ns < w1]
    planes = sorted({e.plane for e in dev})
    busy_ns, gaps = 0.0, []
    spans = [e for e in events if not e.plane.startswith("/device:")
             and e.name != WINDOW_SPAN
             and e.name.startswith(tuple(span_prefixes))
             and e.end_ns > w0 and e.start_ns < w1]
    for plane in planes:
        iv = _union((e.start_ns, e.end_ns) for e in dev if e.plane == plane)
        busy_ns += sum(e - s for s, e in iv)
        edges = [w0] + [x for s, e in iv for x in (s, e)] + [w1]
        for s, e in zip(edges[::2], edges[1::2]):
            if e > s:
                name = _host_span(spans, (s + e) / 2) \
                    if window_s is None else UNTRACED_HOST
                gaps.append((name, (e - s) / 1e9))
    n = max(len(planes), 1)
    return Summary(window_s=(w1 - w0) / 1e9, busy_s=busy_ns / 1e9 / n,
                   devices=len(planes), ops=dev, gaps=gaps)


def _host_span(spans: Sequence[Event], t: float) -> str:
    inside = [e for e in spans if e.start_ns <= t <= e.end_ns]
    if not inside:
        return "(no bench span)"
    return min(inside, key=lambda e: e.dur_ns).name


def trace_window(log_dir: str, window_s: Optional[float] = None
                 ) -> Optional[Summary]:
    """The summary of the one trace under ``log_dir``, or ``None``
    where no device op ran in the window (no device to read)."""
    s = summarize(events_from_xplane(find_xplane(log_dir)),
                  window_s=window_s)
    return s if s.devices else None
