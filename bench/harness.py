"""The cell harness: finds a cell's configuration, traffic mix, driver
and per-layer readers by the names in ``BENCHMARK.json``, runs it once
and builds the result line.

Adding a configuration, a traffic mix or a per-layer metric means
adding a file and an entry in ``BENCHMARK.json``:

* ``bench/configs/<config>.json`` — sizes, the engine settings, the
  ``driver`` that serves it (``bench/drivers/<driver>.py``), the
  control and the limits of the correctness check;
* ``bench/traffic/<mix>.json`` — parameters of ``traffic.py``;
* ``bench/metrics/<metric>.py`` — ``read(r) -> float | None``, given a
  ``Reading``; ``None`` means there was nothing to read.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time
from typing import Any, Dict, List, Optional

import tracereduce

BENCH = os.path.dirname(os.path.abspath(__file__))


class HarnessError(RuntimeError):
    """The benchmark cannot run here (no chip, unknown device, a bad
    name); no result is printed."""


def load_json(path: str) -> Any:
    with open(path) as f:
        return json.load(f)


def load_module(path: str, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not os.path.exists(path):
        raise HarnessError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def device_peak(kind: str, bench_dir: str = BENCH) -> Dict[str, float]:
    """The peaks of ``kind`` from ``peaks.json``; an unknown device is
    an error, never a default."""
    peaks = load_json(os.path.join(bench_dir, "peaks.json"))
    if kind not in peaks:
        raise HarnessError(f"device kind {kind!r} is not in peaks.json "
                           f"(known: {sorted(peaks)})")
    return peaks[kind]


@dataclasses.dataclass
class Check:
    """One number the correctness check compares, with its limit:
    ``kind="max"`` holds when value <= limit, ``"min"`` when >=."""
    name: str
    value: float
    limit: float
    kind: str = "max"

    @property
    def ok(self) -> bool:
        return self.value <= self.limit if self.kind == "max" \
            else self.value >= self.limit


@dataclasses.dataclass
class Outcome:
    """What a driver hands back from one run."""
    end_to_end: Dict[str, float]
    counters: Dict[str, Any]
    checks: List[Check]
    attempted: int
    failed: int
    memory_peak_bytes: int
    summary: Optional[tracereduce.Summary] = None


@dataclasses.dataclass
class Reading:
    """Everything a per-layer reader may read."""
    config: Dict
    peak: Dict[str, float]
    end_to_end: Dict[str, float]
    counters: Dict[str, Any]
    trace: Optional[tracereduce.Summary]


class CompileCounter:
    """Counts XLA backend compiles (JAX's own monitoring events), so a
    driver can report compiles inside its measured window."""
    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.count = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.count += 1


class GcClock:
    """A ``gc.callbacks`` entry that adds up the collector's time."""

    def __init__(self):
        self.seconds, self.n, self._t = 0.0, 0, 0.0

    def __call__(self, phase: str, info: Dict) -> None:
        if phase == "start":
            self._t = time.perf_counter()
        else:
            self.seconds += time.perf_counter() - self._t
            self.n += 1


@dataclasses.dataclass
class Cell:
    """One run of one cell, as a driver sees it."""
    name: str
    config_name: str
    config: Dict
    traffic: Dict
    seed: int
    seconds: float
    trace: bool
    t_start: float                          # perf_counter at process start
    compiles: CompileCounter
    control: bool = False                   # run the config's control

    @contextlib.contextmanager
    def window(self, out: Dict[str, Any], host_spans: bool = True):
        """The measured window: under ``--trace 1`` the profiler runs
        around it (host Python tracing off) and ``out["summary"]``
        receives the reduced trace.  ``host_spans=False`` turns host
        tracing off as well, for a loop whose rate the host tracer
        slows: gaps then carry no host span.  Set-up's garbage is
        collected and what survives it frozen before the window opens,
        so the collector walks only what the window allocates;
        ``out["gc_s"]`` is the time it spent collecting in the window."""
        import jax
        gc.collect()
        gc.freeze()
        gc_clock = GcClock()
        gc.callbacks.append(gc_clock)
        log_dir = tempfile.mkdtemp(prefix="bench_trace_") \
            if self.trace else None
        if log_dir:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1 if host_spans else 0
            jax.profiler.start_trace(log_dir, profiler_options=opts)
        c0 = self.compiles.count
        t0 = time.perf_counter()
        try:
            with jax.profiler.TraceAnnotation(tracereduce.WINDOW_SPAN):
                yield
        finally:
            window_s = time.perf_counter() - t0
            gc.callbacks.remove(gc_clock)
            gc.unfreeze()
            out["compiles_in_window"] = self.compiles.count - c0
            out["gc_s"], out["gc_collections"] = gc_clock.seconds, gc_clock.n
            if log_dir:
                jax.profiler.stop_trace()
                try:
                    out["summary"] = tracereduce.trace_window(
                        log_dir, None if host_spans else window_s)
                finally:
                    shutil.rmtree(log_dir, ignore_errors=True)


def memory_peak_bytes() -> int:
    import jax
    peaks = [int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
             for d in jax.local_devices()]
    return max(peaks) if peaks else 0


def selected(entries: List[Dict], cell: str,
             reported: Optional[set] = None) -> List[Dict]:
    """The metrics a cell reports: those that list it under
    ``workloads``, or, without that key, every cell (end-to-end) or
    every cell reporting the metric it ``moves`` (per-layer)."""
    out = []
    for m in entries:
        if "workloads" in m:
            if cell in m["workloads"]:
                out.append(m)
        elif reported is None or m["moves"] in reported:
            out.append(m)
    return out


def run_cell(root: str, workload: str, seed: int, seconds: float,
             trace: bool, *, peak: Dict[str, float], t_start: float,
             control: bool = False,
             device: Optional[Dict[str, Any]] = None) -> Dict[str, Any]:
    """Run one cell once; returns the result object (the last line)."""
    import jax
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in spec["workloads"]}
    if workload not in cells:
        raise HarnessError(f"no workload {workload!r} in BENCHMARK.json")
    w = cells[workload]
    configs = {c["name"]: c for c in spec["configs"]}
    centry = configs[w["config"]]
    config = load_json(os.path.join(root, centry["file"]))
    bench_dir = os.path.join(root, spec["paths"][0])
    traffic = load_json(os.path.join(bench_dir, "traffic",
                                     f"{w['traffic']}.json"))
    driver = load_module(os.path.join(bench_dir, "drivers",
                                      f"{config['driver']}.py"),
                         f"bench_driver_{config['driver']}")
    e2e = selected(spec["end_to_end"], workload)
    layer = selected(spec["per_layer"], workload,
                     reported={m["name"] for m in e2e})
    readers = {m["name"]: load_module(
        os.path.join(bench_dir, "metrics", f"{m['name']}.py"),
        f"bench_metric_{m['name'].replace('.', '_')}") for m in layer} \
        if trace else {}

    counter = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(counter)
    cell = Cell(name=workload, config_name=w["config"], config=config,
                traffic=traffic, seed=seed, seconds=seconds, trace=trace,
                t_start=t_start, compiles=counter, control=control)
    out: Outcome = driver.run(cell)

    metrics: Dict[str, Dict[str, Any]] = {}
    if trace:
        reading = Reading(config=config, peak=peak,
                          end_to_end=out.end_to_end,
                          counters=out.counters, trace=out.summary)
        for m in layer:
            v = readers[m["name"]].read(reading)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in e2e:
            if m["name"] not in out.end_to_end:
                raise HarnessError(f"driver {config['driver']!r} gave no "
                                   f"{m['name']!r}")
            metrics[m["name"]] = {"value": out.end_to_end[m["name"]],
                                  "unit": m["unit"]}
    dev = dict(device or {})
    dev["memory_peak_bytes"] = out.memory_peak_bytes
    result: Dict[str, Any] = {
        "correct": all(c.ok for c in out.checks) and not out.failed,
        "attempted": out.attempted, "failed": out.failed,
        "metrics": metrics, "device": dev,
    }
    if trace and out.summary is not None:
        dev["busy_s"] = out.summary.busy_s
        dev["window_s"] = out.summary.window_s
        result["breakdown"] = {"device_ops": out.summary.top_ops(),
                               "idle_gaps": out.summary.top_gaps()}
    result["window"] = {k: v for k, v in out.counters.items()
                        if k in WINDOW_KEYS}
    result["checks"] = {c.name: {"value": c.value, "limit": c.limit}
                        for c in out.checks}
    return result


#: counters copied into the result line (for the reader of a run)
WINDOW_KEYS = ("compiles_in_window", "gc_s", "gc_collections", "iterations",
               "iterations_at_p95", "step_ms", "window_s", "tokens",
               "gaps", "frames", "requests_finished", "setup_phases_s",
               "decode_calls", "prefill_calls", "requests_compared",
               "check_s", "max_logit_gap", "mean_logit_gap",
               "off_first_choice")


def check_lines(result: Dict[str, Any]) -> List[str]:
    return [f"check {name} {c['value']!r} limit {c['limit']!r}"
            for name, c in result["checks"].items()]


def open_chip(root: str, chips: int):
    """The device line of a result and the device's peaks, once JAX
    finds at least ``chips`` TPU devices of a kind ``peaks.json``
    lists; the compile cache goes to ``.jax_cache`` in the checkout
    (a fixed path, so every later run of a cell hits it)."""
    import jax
    devices = jax.devices()
    d0 = devices[0]
    if d0.platform != "tpu":
        raise HarnessError(f"no TPU found (JAX platform {d0.platform!r})")
    if len(devices) < chips:
        raise HarnessError(f"{chips} chips needed, found {len(devices)}")
    peak = device_peak(d0.device_kind)
    jax.config.update("jax_compilation_cache_dir",
                      os.path.join(root, ".jax_cache"))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return {"platform": d0.platform, "kind": d0.device_kind,
            "count": len(devices)}, peak


def main(args, *, t_start: float, root: str) -> int:
    spec = load_json(os.path.join(root, "BENCHMARK.json"))
    chips = {w["name"]: w["chips"] for w in spec["workloads"]}
    if args.workload not in chips:
        raise HarnessError(f"no workload {args.workload!r}")
    device, peak = open_chip(root, chips[args.workload])
    result = run_cell(root, args.workload, args.seed, args.seconds,
                      bool(args.trace), peak=peak, t_start=t_start,
                      device=device)
    for line in check_lines(result):
        print(line, file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(result))
    return 0


def now() -> float:
    return time.perf_counter()
