#!/usr/bin/env python3
"""Time the SDV GEMM/GEMV kernel per call on one TPU at several tile
targets (``kernels.ops.sdv_blocks``), on granite-8b's projections.

For every (plan, K -> M projection, rows) it runs ``ops.packed_matmul``
on the kernel route with the blocks of each target (``--targets``,
vregs per ``[br, bg]`` array) and with the fixed ``bg=128, bk=512``
blocks the kernel had before the rule, checks every output bitwise
against the fixed blocks' and the fixed blocks' against the jnp ref
route, and prints one JSON line per case: blocks, vregs per step, K
steps (loop iterations) per call, median ms per call and ns per step.
Times are the host clock around ``block_until_ready`` (median of
``--reps`` calls after one warm call), so a few tens of microseconds
of dispatch sit in each.

Usage (one TPU; exits 2 without one):
  python scripts/sdv_tile_sweep.py [--rows 8,16,64] [--targets 8,16,32]
      [--plans dsp48e2,int32] [--reps 5] [--out chiprun_out/sweep.jsonl]
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "..", "src"))

import jax                                            # noqa: E402
import jax.numpy as jnp                               # noqa: E402
import numpy as np                                    # noqa: E402

from repro.core.datapath import DSP48E2, INT32, plan_sdv      # noqa: E402
from repro.kernels import ops                                 # noqa: E402

PLANS = {"dsp48e2": DSP48E2, "int32": INT32}
#: granite-8b-code's decoder projections, K -> M (q/o, k/v, gate/up, down)
GRANITE = [(4096, 4096), (4096, 1024), (4096, 14336), (14336, 4096)]


def _time(fn, reps):
    jax.block_until_ready(fn())
    ts = []
    for _ in range(reps):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        ts.append(time.perf_counter() - t0)
    return statistics.median(ts) * 1e3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", default="8,16,64")
    ap.add_argument("--targets", default="8,16,32")
    ap.add_argument("--plans", default="dsp48e2")
    ap.add_argument("--reps", type=int, default=5)
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    if jax.default_backend() != "tpu":
        print("no TPU found", file=sys.stderr)
        return 2
    rng = np.random.default_rng(0)
    out = open(args.out, "w") if args.out else None
    ok = True
    for pname in args.plans.split(","):
        plan = plan_sdv(PLANS[pname], 4, 8, signed_a=True, signed_b=True,
                        park_sign_bits=True)
        for k, m in GRANITE:
            w_int = jnp.asarray(rng.integers(-8, 8, (m, k)), jnp.int32)
            words = jax.jit(ops.prepare_sdv_weights,
                            static_argnums=1)(w_int, plan)
            g = words.shape[-1]
            for rows in map(int, args.rows.split(",")):
                x = jnp.asarray(rng.integers(-128, 128, (rows, k)),
                                jnp.int32)
                cases = [("fixed", min(rows, 128), 128, 512)]
                for t in map(int, args.targets.split(",")):
                    cases.append((f"t{t}",) + ops.sdv_blocks(
                        rows, k, g, plan, target_vregs=t))
                want = ops.packed_matmul(x, words, plan=plan, m=m,
                                         mode="ref")
                first = None
                for name, br, bg, bk in cases:
                    def call(br=br, bg=bg, bk=bk):
                        return ops.packed_matmul(
                            x, words, plan=plan, m=m, block_rows=br,
                            block_g=bg, block_k=bk)
                    y = call()
                    first = y if first is None else first
                    exact = bool(jnp.array_equal(y, first)) and bool(
                        jnp.array_equal(first, want))
                    ok &= exact
                    ms = _time(call, args.reps)
                    bg_eff = min(bg, g)
                    steps = (-(-rows // br)) * (-(-g // bg_eff)) * k
                    line = {
                        "plan": pname, "k": k, "m": m, "g": g,
                        "rows": rows, "case": name, "br": br, "bg": bg_eff,
                        "bk": bk,
                        "vregs": -(-br // 8) * -(-bg_eff // 128),
                        "steps": steps, "ms": round(ms, 4),
                        "ns_per_step": round(ms * 1e6 / steps, 1),
                        "exact": exact}
                    print(json.dumps(line), flush=True)
                    if out:
                        out.write(json.dumps(line) + "\n")
    if out:
        out.close()
    print(json.dumps({"ok": ok, "device": jax.devices()[0].device_kind}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
