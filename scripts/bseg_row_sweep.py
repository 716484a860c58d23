#!/usr/bin/env python3
"""Time the BSEG conv2d kernel per call on one TPU at several row
blocks (``kernels.ops.bseg_blocks``), on UltraNet's 416x416 3x3 stages.

For every stage shape and every row block ``bh`` in ``--bh`` it runs
``ops.packed_conv2d`` on the ``bseg_conv2d`` route, checks the output
bitwise against the jnp ref route, and prints one JSON line per case:
the block, loop iterations per call (row blocks x steps; one iteration
runs every tap group's word), vregs an iteration works on (tap groups
x ``ceil(khc/8) * ceil(bh*bco/128)`` per limb), ms per call and ns per
iteration.  ``bh`` 0 is the call with no block given, so the rule's
own choice, and a checkout that has no rule, time the same way; there
an iteration runs one tap group's word of one row, as the kernel did
before the row block.  A call's time is the host clock over ``--reps``
calls dispatched back to back and waited for once, the median of
three such rounds after one warm call.

Usage (one TPU; exits 2 without one):
  python scripts/bseg_row_sweep.py [--bh 0,1,2,4,8,16,32,64]
      [--plan int32] [--reps 10] [--src DIR] [--out chiprun_out/x.jsonl]

``--src`` puts another checkout's ``src`` first on the path (say, the
parent commit unpacked under a git-ignored directory), to time its
kernel on the same chip; only ``--bh 0`` runs where ``ops`` has no
``bseg_blocks``.
"""
from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

#: UltraNet 416's distinct 3x3 stage shapes (h = w, C_in, C_out) and how
#: many stages of the frame have each (``models.ultranet``).
STAGES = [(416, 3, 16, 1), (208, 16, 32, 1), (104, 32, 64, 1),
          (52, 64, 64, 1), (26, 64, 64, 4)]


def _time(fn, reps):
    import jax
    jax.block_until_ready(fn())
    rounds = []
    for _ in range(3):
        t0 = time.perf_counter()
        for _ in range(reps - 1):
            fn()
        jax.block_until_ready(fn())
        rounds.append((time.perf_counter() - t0) / reps)
    return statistics.median(rounds) * 1e3


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--bh", default="0,1,2,4,8,16,32,64")
    ap.add_argument("--plan", default="int32")
    ap.add_argument("--reps", type=int, default=10)
    ap.add_argument("--src", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "..", "src"))
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.abspath(args.src))

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.datapath import DATAPATHS, plan_bseg
    from repro.kernels import bseg_common, ops

    if jax.default_backend() != "tpu":
        print("no TPU found", file=sys.stderr)
        return 2
    plan = plan_bseg(DATAPATHS[args.plan], 4, 4)
    limbs = bseg_common.word_spec(plan).limbs
    rng = np.random.default_rng(0)
    out = open(args.out, "w") if args.out else None
    ok = True
    for h, cin, cout, count in STAGES:
        x = jnp.asarray(rng.integers(0, 16, (1, h, h, cin)), jnp.int32)
        w = jnp.asarray(rng.integers(-8, 8, (cout, cin, 3, 3)), jnp.int32)
        want = ops.packed_conv2d(x, w, plan=plan, mode="ref")
        khc, bco = 3 * cin, min(128, cout)
        n_groups = -(-3 // plan.n_k)
        n_steps = -(-(h + plan.n_k - 1) // plan.n_i)
        need = (n_steps - 1) * plan.n_i + (n_groups - 1) * plan.n_k \
            + plan.n_i
        w_pad = h + 1 + max(1, need - h - 1)       # as ops.packed_conv2d
        blocked = hasattr(ops, "bseg_blocks")
        for bh in map(int, args.bh.split(",")):
            if bh > h or (bh and not blocked):
                continue
            kw = {"block_h": bh} if bh else {}
            conv = jax.jit(lambda x, w, kw=kw: ops.packed_conv2d(
                x, w, plan=plan, mode="bseg_conv2d", **kw))
            exact = bool(jnp.array_equal(conv(x, w), want))
            ok &= exact
            ms = _time(lambda: conv(x, w), args.reps)
            if blocked:
                bh_eff = bh or ops.bseg_blocks(h, w_pad, khc, bco, n_groups,
                                               plan)
                words = n_groups
            else:
                bh_eff, words = 1, 1
            iters = (-(-h // bh_eff) * (cout // bco) * n_steps
                     * n_groups // words)
            vregs = words * limbs * -(-khc // 8) * -(-(bh_eff * bco) // 128)
            line = {"plan": args.plan, "h": h, "cin": cin, "cout": cout,
                    "stages": count, "bh": bh, "bh_eff": bh_eff,
                    "vregs": vregs, "iters": iters, "ms": round(ms, 4),
                    "ns_per_iter": round(ms * 1e6 / iters, 1),
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
