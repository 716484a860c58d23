"""Reproductions of the paper's tables/figures (deliverable d).

One function per artifact; each returns a list of CSV rows
(name, us_per_call, derived) — us_per_call measures the live JAX
computation backing the artifact where one exists.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import (DSP48E2, DSP58, FP32M, INT32, bseg_density,
                        plan_bseg, plan_sdv, sdv_density, sdv_matvec,
                        bseg_conv1d)
from repro.finnlite import bseg_conv_unit, sdv_matvec_unit, ultranet_tables
from repro.finnlite.resource import PAPER_TAB2
from repro.models.ultranet import ultranet_multiplies


def _time(fn, *a, n=3):
    fn(*a)  # warmup/compile
    t0 = time.perf_counter()
    for _ in range(n):
        r = fn(*a)
    jax.block_until_ready(r)
    return (time.perf_counter() - t0) / n * 1e6


# ---------------------------------------------------------------------------
# Fig. 5 — operational density vs precision
# ---------------------------------------------------------------------------

def fig5_density():
    rows = []
    # paper anchor points asserted (Sec. II / IV-B):
    assert sdv_density(DSP48E2, 8, 8) == 2, "INT8 SDV must match [13]"
    assert sdv_density(DSP48E2, 4, 4) == 4
    assert plan_bseg(DSP48E2, 4, 4).density == 6
    for spec in (DSP48E2, DSP58, INT32, FP32M):
        for w in range(1, 9):
            try:
                sd = sdv_density(spec, w, w) if spec.exact_wrap else 0
            except ValueError:
                sd = 0
            bd = bseg_density(spec, max(w, 1), max(w, 1))
            rows.append((f"fig5.sdv.{spec.name}.w{w}", 0.0, sd))
            rows.append((f"fig5.bseg.{spec.name}.w{w}", 0.0, bd))
    return rows


# ---------------------------------------------------------------------------
# Fig. 8 — SDV LUT scaling (precision / matrix size)
# ---------------------------------------------------------------------------

def fig8_sdv_scaling():
    rows = []
    rng = np.random.default_rng(0)
    for w in range(2, 9):
        est = sdv_matvec_unit(24, 24, w, w, cycles=3)
        # live check: the packed matvec at this precision, through the
        # core int64 *oracle* (x64 scoped here; the serving kernels run
        # the same wide words as 2-limb int32 — see kernelbench)
        with jax.enable_x64(True):
            plan = plan_sdv(DSP48E2, w, w)
            wm = jnp.asarray(
                rng.integers(-(1 << w - 1), 1 << w - 1, (24, 24)))
            x = jnp.asarray(rng.integers(-(1 << w - 1), 1 << w - 1, (24,)))
            us = _time(lambda: sdv_matvec(wm, x, plan))
        rows.append((f"fig8.precision.w{w}.lut", us, est.lut))
        rows.append((f"fig8.precision.w{w}.dsp", 0.0, est.dsp))
    for m in (8, 16, 24, 32, 40, 48):
        est = sdv_matvec_unit(m, m, 4, 4, cycles=3)
        rows.append((f"fig8.matrix.{m}x{m}.lut", 0.0, est.lut))
        rows.append((f"fig8.matrix.{m}x{m}.dsp", 0.0, est.dsp))
    return rows


# ---------------------------------------------------------------------------
# Fig. 9 — BSEG LUT scaling (precision / kernel size)
# ---------------------------------------------------------------------------

def fig9_bseg_scaling():
    rows = []
    rng = np.random.default_rng(0)
    for w in range(2, 9):
        est = bseg_conv_unit(128, 8, 16, 1500, w, w, out_per_cycle=8)
        # core int64 oracle timing (x64 scoped; kernels are 2-limb)
        with jax.enable_x64(True):
            plan = plan_bseg(DSP48E2, w, w)
            taps = jnp.asarray(
                rng.integers(-(1 << w - 1), 1 << w - 1, (16, 8)))
            xs = jnp.asarray(rng.integers(0, 1 << w, (16, 256)))
            us = _time(lambda: bseg_conv1d(taps, xs, plan))
        rows.append((f"fig9.precision.w{w}.lut", us, est.lut))
        rows.append((f"fig9.precision.w{w}.dsp", 0.0, est.dsp))
    for k in (2, 4, 8, 16, 32):
        est = bseg_conv_unit(128, k, 16, 1500, 4, 4, out_per_cycle=8)
        rows.append((f"fig9.kernel.k{k}.lut", 0.0, est.lut))
        rows.append((f"fig9.kernel.k{k}.dsp", 0.0, est.dsp))
    return rows


# ---------------------------------------------------------------------------
# Tab. II — UltraNet full-model comparison
# ---------------------------------------------------------------------------

def tab2_ultranet():
    rows = []
    m = ultranet_multiplies(416, 416, mode="bseg")
    n = ultranet_multiplies(416, 416, mode="naive")
    for name, p in PAPER_TAB2.items():
        rows.append((f"tab2.paper.{name}.lut", 0.0, p["lut"]))
        rows.append((f"tab2.paper.{name}.fps_per_dsp", 0.0,
                     round(p["fps"] / p["dsp"], 2)))
    # our measured packed-multiply reduction for the full model
    rows.append(("tab2.ours.macs_per_frame", 0.0, m["total_macs"]))
    rows.append(("tab2.ours.wide_mults_per_frame", 0.0, m["total_mults"]))
    rows.append(("tab2.ours.density_int32", 0.0,
                 round(m["density_achieved"], 3)))
    rows.append(("tab2.ours.naive_mults", 0.0, n["total_mults"]))
    # paper's headline: FPS/DSP 1.1 -> 1.5 (+36%), LUT -21%
    rows.append(("tab2.paper.fps_per_dsp_gain", 0.0,
                 round(1.5 / 1.1 - 1, 3)))
    rows.append(("tab2.paper.lut_reduction", 0.0,
                 round(1 - 50000 / 63000, 3)))
    return rows


def tab3_layers():
    rows = []
    t = ultranet_tables()
    for li, row in t["tab3"].items():
        p = row["paper"]
        rows.append((f"tab3.L{li}.model_finn_lut", 0.0,
                     row["model_finn_lut"]))
        rows.append((f"tab3.L{li}.paper_finn_lut", 0.0, p[0]))
        rows.append((f"tab3.L{li}.model_b1_lut", 0.0, row["model_b1_lut"]))
        rows.append((f"tab3.L{li}.paper_b1_lut", 0.0, p[1]))
        rows.append((f"tab3.L{li}.model_b2_lut", 0.0, row["model_b2_lut"]))
        rows.append((f"tab3.L{li}.paper_b2_lut", 0.0, p[2]))
    return rows


def tab4_maxfreq():
    t = ultranet_tables()["tab4"]
    m, p = t["model"], t["paper"]
    rows = [
        ("tab4.model.finn_lut", 0.0, m["finn_lut"]),
        ("tab4.paper.finn_lut", 0.0, p["finn"]["lut"]),
        ("tab4.model.finn_dsp", 0.0, m["finn_dsp"]),
        ("tab4.paper.finn_dsp", 0.0, p["finn"]["dsp"]),
        ("tab4.model.bseg_lut", 0.0, m["bseg_lut"]),
        ("tab4.paper.bseg_lut", 0.0, p["bseg"]["lut"]),
        ("tab4.model.bseg_dsp", 0.0, m["bseg_dsp"]),
        ("tab4.paper.bseg_dsp", 0.0, p["bseg"]["dsp"]),
        # paper headline: -63% LUT, -25% DSP at max frequency
        ("tab4.model.lut_reduction", 0.0,
         round(1 - m["bseg_lut"] / m["finn_lut"], 3)),
        ("tab4.paper.lut_reduction", 0.0,
         round(1 - p["bseg"]["lut"] / p["finn"]["lut"], 3)),
        ("tab4.model.dsp_reduction", 0.0,
         round(1 - m["bseg_dsp"] / m["finn_dsp"], 3)),
        ("tab4.paper.dsp_reduction", 0.0,
         round(1 - p["bseg"]["dsp"] / p["finn"]["dsp"], 3)),
    ]
    return rows
