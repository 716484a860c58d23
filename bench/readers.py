"""Arithmetic shared by the per-layer readers in ``bench/metrics/``.

A reader returns ``None`` where its run has nothing to read (no trace,
no device op, no event of its kernel), so the harness leaves the metric
out; a share of a roofline or of a peak is never given as 0 for want of
a reading.
"""
from __future__ import annotations

from typing import Optional

import work


def idle_share(r) -> Optional[float]:
    """Percent of the traced window in which no op ran on the device."""
    if r.trace is None or r.trace.busy_s <= 0:
        return None
    return 100.0 * r.trace.idle_share()


def roofline(r, pattern: str, least_s: float) -> Optional[float]:
    """Percent of the least time ``least_s`` (from ``work.py``) in the
    device time of the ops matching ``pattern``."""
    if r.trace is None:
        return None
    t = r.trace.op_seconds(pattern)
    if t <= 0:
        return None
    return 100.0 * least_s / t


def decode_linear_least_s(r) -> float:
    """Least time of every decoder-layer projection the window
    dispatched: decode steps over the whole bucket, prefill chunks over
    one slot's chunk.  The LM head is left out: the program multiplies
    it in XLA (``transformer._unembed``), not in a packed kernel."""
    c, peak = r.counters, r.peak
    integer = r.config["engine"]["act_bits"] < 16
    dec = work.step_linear_work(r.config, c["batch"], head=False)
    pre = work.step_linear_work(r.config, c["prefill_chunk"], head=False)
    return (c["decode_calls"] * work.least_time(dec, peak, integer=integer)[0]
            + c["prefill_calls"]
            * work.least_time(pre, peak, integer=integer)[0])


def decode_mfu(r) -> Optional[float]:
    """Percent of the int8 peak in ``tokens_per_s`` times the model
    operations of a token, averaged over the tokens the window emitted
    (each at its own context)."""
    ctx = r.counters["contexts"]
    if not ctx:
        return None
    ops = sum(work.model_ops_per_token(r.config, c) for c in ctx) / len(ctx)
    return 100.0 * r.end_to_end["tokens_per_s"] * ops \
        / r.peak["int8_ops_per_s"]
