"""Launch-path tests: dry-run cell construction (specs, shardings,
shape-skip logic) without the 512-device compile — the full compile
matrix runs via `python -m repro.launch.dryrun`.  These tests run on
the subprocess mesh."""
import json
import os
import subprocess
import sys

import pytest

SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import json
import jax

# a miniature production mesh with the same axis names
from repro.launch.mesh import make_mesh
mesh = make_mesh((4, 2), ("data", "model"))

from repro.configs.base import SHAPES
from repro.configs.registry import ARCHS, get_arch
from repro.launch import dryrun as DR

out = {"built": [], "skips": []}
for arch in ("tinyllama-1.1b", "mamba2-130m", "phi3.5-moe-42b-a6.6b"):
    cfg = get_arch(arch).reduced()
    import dataclasses
    cfg = dataclasses.replace(cfg, name=arch)
    for shape_name in ("train_4k", "decode_32k"):
        shape = dataclasses.replace(SHAPES[shape_name], seq_len=64,
                                    global_batch=8)
        rules, fn, args, in_sh, donate = DR.build_cell(cfg, shape, mesh)
        # structural checks: shardings tree matches args tree
        la = len(jax.tree_util.tree_leaves(args))
        ls = len(jax.tree_util.tree_leaves(
            in_sh, is_leaf=lambda x: hasattr(x, "spec")))
        out["built"].append([arch, shape_name, la, ls])
        # the cell actually lowers + compiles on the tiny mesh
        from repro.models import shard_ctx
        with mesh:
            with shard_ctx.use_rules(rules):
                c = jax.jit(fn, in_shardings=in_sh,
                            donate_argnums=donate).lower(*args).compile()
        assert c.cost_analysis().get("flops", 0) > 0

# skip rules propagate
for a in ARCHS.values():
    okay, why = a.shape_supported(SHAPES["long_500k"])
    if not okay:
        out["skips"].append(a.name)
print("RESULT " + json.dumps(out))
"""


@pytest.fixture(scope="module")
def build_result():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run([sys.executable, "-c", SCRIPT], env=env,
                          capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-4000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][-1]
    return json.loads(line[len("RESULT "):])


def test_cells_build_and_compile(build_result):
    assert len(build_result["built"]) == 6
    for arch, shape, la, ls in build_result["built"]:
        assert la == ls, (arch, shape, "args/shardings tree mismatch")


def test_long_context_skips(build_result):
    skips = set(build_result["skips"])
    assert "qwen2.5-32b" in skips
    assert "mamba2-130m" not in skips
    assert "recurrentgemma-2b" not in skips
