"""The harness: cells, configurations, mixes and metrics found by name
from files alone; no result without a chip or on an unknown device."""
import json
import os
import subprocess
import sys

import pytest

import benchtest
import harness

COUNTER_METRIC = '''"""Iterations of the window (a test adds it as a file)."""


def read(r):
    return float(r.counters["iterations"])
'''


def test_a_cell_config_mix_and_metric_added_as_files(tmp_path):
    root = benchtest.make_root(
        tmp_path, configs={"tiny-dec": benchtest.tiny_decoder()},
        mixes={"tiny_mix": benchtest.TINY_MIX},
        metrics={"window_iterations.decode": COUNTER_METRIC},
        cells=[("tiny.decode", "tiny-dec", "tiny_mix")],
        per_layer=[{"name": "window_iterations.decode", "unit": "1",
                    "better": "higher", "source": "program_counter",
                    "layer": "engine (serving/engine.py)",
                    "moves": "tokens_per_s", "workloads": ["tiny.decode"]}])
    timed = benchtest.run(root, "tiny.decode")
    assert timed["correct"] and timed["failed"] == 0
    assert set(timed["metrics"]) == {"tokens_per_s", "itl_p95_ms",
                                     "setup_s"}
    assert timed["window"]["compiles_in_window"] == 0
    assert list(timed)[-1] == "checks"
    traced = benchtest.run(root, "tiny.decode", trace=True)
    got = traced["metrics"]
    assert got["window_iterations.decode"]["value"] \
        == traced["window"]["iterations"] > 0
    # on the CPU there is no device trace to read: those metrics are
    # left out, never reported as 0
    assert "device_idle_share.decode" not in got
    assert "sdv_roofline.decode" not in got
    assert 0 < got["batch_occupancy.decode"]["value"] <= 100


def test_unknown_device_kind_fails():
    with pytest.raises(harness.HarnessError, match="not in peaks.json"):
        harness.device_peak("TPU v0 imaginary")


def _run_py(cwd, *extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    return subprocess.run(
        [sys.executable, os.path.join(cwd, "bench", "run.py"),
         "--workload", "ultranet.stream", "--seed", str(2**33 + 1),
         "--seconds", "1", "--trace", "0", *extra],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_no_chip_no_result():
    p = _run_py(benchtest.ROOT)
    assert p.returncode != 0
    assert "no TPU" in p.stderr and not p.stdout.strip()


def test_bench_files_alone_give_no_result(tmp_path):
    """A directory holding only BENCHMARK.json and bench/ (no program)
    exits non-zero and prints no result."""
    root = benchtest.make_root(tmp_path)
    assert sorted(os.listdir(root)) == ["BENCHMARK.json", "bench"]
    p = _run_py(root)
    assert p.returncode != 0 and not p.stdout.strip()


def test_benchmark_json_names_resolve():
    spec = json.load(open(os.path.join(benchtest.ROOT, "BENCHMARK.json")))
    bench = os.path.join(benchtest.ROOT, spec["paths"][0])
    configs = {c["name"]: c for c in spec["configs"]}
    for w in spec["workloads"]:
        cfg = json.load(open(os.path.join(benchtest.ROOT,
                                          configs[w["config"]]["file"])))
        assert os.path.exists(os.path.join(bench, "drivers",
                                           f"{cfg['driver']}.py"))
        assert os.path.exists(os.path.join(bench, "traffic",
                                           f"{w['traffic']}.json"))
        for key in ("checks", "control", "engine"):
            assert key in cfg, (w["name"], key)
    for m in spec["per_layer"]:
        assert os.path.exists(os.path.join(bench, "metrics",
                                           f"{m['name']}.py")), m["name"]


def test_window_rate_credits_the_step_across_the_close():
    from drivers import decode
    # 16 tokens per 1-s step from t=0; the window closes at 2.5 s, in
    # the middle of the third step: half its tokens count
    steps = [(0.0, 1.0, 16), (1.0, 2.0, 16), (2.0, 3.0, 16)]
    assert decode.window_rate(steps, 0.0, 2.5) == pytest.approx(16.0)
    # a step that ends just before or just after the close moves the
    # rate by a hair, not by a whole step's tokens
    early = [(0.0, 1.2, 16), (1.2, 2.499, 16)]
    late = [(0.0, 1.2, 16), (1.2, 2.501, 16)]
    assert decode.window_rate(early, 0.0, 2.5) == pytest.approx(
        decode.window_rate(late, 0.0, 2.5), rel=2e-3)
