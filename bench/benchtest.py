"""Helpers for the benchmark's tests: a checkout root in a temporary
directory holding a copy of ``bench/`` and a ``BENCHMARK.json`` with
extra cells, made from files only, as a later change would add them."""
from __future__ import annotations

import copy
import json
import os
import shutil
import time
from typing import Dict, Optional

import harness

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)

#: a decoder small enough for the CPU, same layout as the granite files
TINY_DECODER = {"hidden_size": 128, "intermediate_size": 256,
                "num_attention_heads": 4, "num_key_value_heads": 2,
                "num_hidden_layers": 2, "vocab_size": 512}
TINY_MIX = {"kind": "closed_loop",
            "prompt": {"dist": "lognormal", "median": 6, "sigma": 0.6,
                       "min": 2, "max": 12},
            "output": {"dist": "lognormal", "median": 8, "sigma": 0.6,
                       "min": 4, "max": 24},
            "first_round": "residual", "rounds": 8}


def tiny_decoder(base: str = "granite-8b-d9-sdv", **engine) -> Dict:
    cfg = json.load(open(os.path.join(BENCH, "configs", f"{base}.json")))
    cfg.update(TINY_DECODER)
    cfg["engine"].update(batch=4, s_max=64, **engine)
    cfg["checks"]["min_tokens_compared"] = 10
    return cfg


def make_root(tmp, *, configs: Dict[str, Dict] = {},
              mixes: Dict[str, Dict] = {}, metrics: Dict[str, str] = {},
              cells: tuple = (), per_layer: tuple = ()) -> str:
    """A checkout root under ``tmp``: the repository's ``bench/`` and
    ``BENCHMARK.json`` plus the given files and entries.  ``cells`` are
    (name, config, mix); every metric of the first granite cell also
    lists them."""
    root = os.path.join(str(tmp), "checkout")
    shutil.copytree(BENCH, os.path.join(root, "bench"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    spec = json.load(open(os.path.join(ROOT, "BENCHMARK.json")))
    for name, cfg in configs.items():
        with open(os.path.join(root, "bench", "configs", f"{name}.json"),
                  "w") as f:
            json.dump(cfg, f)
        spec["configs"].append({"name": name, "source": "test",
                                "file": f"bench/configs/{name}.json",
                                "reduced": [], "why": "test"})
    for name, mix in mixes.items():
        with open(os.path.join(root, "bench", "traffic", f"{name}.json"),
                  "w") as f:
            json.dump(mix, f)
    for name, src in metrics.items():
        with open(os.path.join(root, "bench", "metrics", f"{name}.py"),
                  "w") as f:
            f.write(src)
    for name, config, mix in cells:
        spec["workloads"].append({"name": name, "config": config,
                                  "traffic": mix, "chips": 1,
                                  "why": "test"})
        kind = configs[config]["driver"]
        like = ("granite8b-sdv.decode" if kind == "decode"
                else "ultranet.stream")
        for m in spec["end_to_end"] + spec["per_layer"]:
            if like in m.get("workloads", ()):
                m["workloads"].append(name)
    spec["per_layer"].extend(copy.deepcopy(list(per_layer)))
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(spec, f)
    return root


def run(root: str, cell: str, *, seed: int = 2**31 + 7,
        seconds: float = 1.0, trace: bool = False,
        control: bool = False, peak: Optional[Dict] = None) -> Dict:
    """One run of ``cell`` on whatever JAX finds (the chip check is the
    command's, not ``run_cell``'s)."""
    return harness.run_cell(root, cell, seed, seconds, trace,
                            peak=peak or harness.device_peak("TPU v5 lite"),
                            t_start=time.perf_counter(), control=control,
                            device={"platform": "cpu"})
