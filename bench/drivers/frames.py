"""Driver: a closed loop of single frames through the jitted UltraNet
forward (``repro.models.ultranet``).

Each frame's input goes to the device, the forward runs, and its head
output comes back to the host before the next frame starts, so the
transfers are part of every frame.  The frames cycle through a pool
made from the seed.  After the window every frame's output is compared
with the plain reference's output for its pool frame: the count of
values that differ must be 0.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import numpy as np

import harness
import reference
import traffic
import weights
from harness import Check, Outcome


def _program_matches(cfg: Dict) -> None:
    """The program fixes UltraNet's shapes and widths in code; refuse a
    configuration file that states others."""
    from repro.models import ultranet as U
    stated = [tuple(s) for s in cfg["stages"]]
    if stated != [tuple(s) for s in U.ULTRANET_LAYERS] \
            or cfg["head_channels"] != U.HEAD_CHANNELS \
            or (cfg["weight_bits"], cfg["act_bits"]) != (U.W_BITS, U.A_BITS):
        raise harness.HarnessError("configuration differs from the "
                                   "program's UltraNet")


def run(cell: harness.Cell) -> Outcome:
    from repro.models import ultranet as U

    cfg, mix = cell.config, cell.traffic
    _program_matches(cfg)
    phases: Dict[str, float] = {}
    t = harness.now()
    phases["start_s"] = t - cell.t_start
    convs, head = weights.ultranet_weights(cfg, cell.seed)
    pool = traffic.frame_pool(mix, cfg, cell.seed)
    jax.block_until_ready((convs, head))
    phases["weights_s"] = harness.now() - t
    mode = cfg["engine"]["mode"]
    fwd = jax.jit(lambda cs, hd, img: U.ultranet_forward(
        U.UltraNetParams(convs=list(cs), head=hd), img, mode=mode))
    t = harness.now()
    np.asarray(fwd(convs, head, jax.device_put(pool[0])))
    phases["warmup_s"] = harness.now() - t

    win: Dict = {}
    outs: List[np.ndarray] = []
    # host tracing slows this loop by a third (a frame is one dispatch
    # and two transfers), so the traced run records device ops only
    with cell.window(win, host_spans=False):
        t0 = harness.now()
        while harness.now() - t0 < cell.seconds:
            x = jax.device_put(pool[len(outs) % len(pool)])
            y = fwd(convs, head, x)
            outs.append(np.asarray(y)[0])
        t1 = harness.now()
    window_s = t1 - t0
    counters = {"frames": len(outs), "window_s": window_s,
                **{k: v for k, v in win.items() if k != "summary"},
                "setup_phases_s": phases}
    mem_peak = harness.memory_peak_bytes()
    del convs, head, fwd
    t = harness.now()
    ref = reference.ultranet_frames(cfg, cell.seed, pool)
    if cell.control:
        # the reference at the lower precision in the program's place
        outs = list(reference.ultranet_frames(
            cfg, cell.seed, pool, round_to=cfg["control"]["round_to"]))
    bad = sum(int(np.count_nonzero(o != ref[i % len(pool)]))
              for i, o in enumerate(outs))
    counters["check_s"] = harness.now() - t
    checks = [Check("mismatched_values", bad,
                    cfg["checks"]["mismatched_values"]),
              Check("frames_compared", len(outs),
                    cfg["checks"]["min_frames_compared"], kind="min")]
    return Outcome(end_to_end={"setup_s": t0 - cell.t_start,
                               "frames_per_s": len(outs) / window_s},
                   counters=counters, checks=checks, attempted=len(outs),
                   failed=0, memory_peak_bytes=mem_peak,
                   summary=win.get("summary"))
