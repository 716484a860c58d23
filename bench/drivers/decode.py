"""Driver: a decoder served by ``repro.serving.Engine`` under a closed
loop, one client per slot of a single bucket.

Set-up makes the weights on the device from the seed, builds the
engine (packing, plans), warms the bucket's programs, and serves every
client's first request until each has its first token, so the window
opens with the chip decoding.  In the window each ``Engine.step()`` is
one iteration; a finished request's client submits its next request at
once, and the engine joins it mid-wave (prefill in chunks beside the
others' decode).  The time a step returns is the time of every token
it emitted.

After the window the engine is freed and the plain reference scores a
seed-drawn sample of the requests (always the longest) over their
prompt and every token served: the widest gap by which a served
token's logit lies below the reference's best is compared with the
configuration's limit.
"""
from __future__ import annotations

import gc
import math
from typing import Dict, List

import jax
import numpy as np

import harness
import reference
import traffic
import weights
from harness import Check, Outcome


def nearest_rank(values: List[float], q: float) -> float:
    """The smallest value with at least q% of the sample at or below
    it (rank ceil(q/100 * n), one-based)."""
    vals = sorted(values)
    if not vals:
        raise ValueError("no values")
    return vals[max(1, min(len(vals), math.ceil(q / 100 * len(vals)))) - 1]


def window_rate(steps: List[tuple], t0: float, seconds: float) -> float:
    """Tokens per second over a window of exactly ``seconds`` from
    ``t0``, given (start, end, tokens) per step: a step's tokens count
    for the share of its span inside the window, so the step that ends
    past the close is neither all in nor all out."""
    t_close = t0 + seconds
    return sum(n * (min(b, t_close) - a) / (b - a)
               for a, b, n in steps if b > a and a < t_close) / seconds


def arch_config(name: str, cfg: Dict):
    """The program's ``ArchConfig`` for a decoder configuration file;
    refuses settings the program cannot run as stated."""
    from repro.configs.base import ArchConfig
    if cfg["hidden_act"] != "silu":
        raise harness.HarnessError(f"hidden_act {cfg['hidden_act']!r}")
    if cfg["rms_norm_eps"] != 1e-6 or cfg["attention_bias"] \
            or cfg["mlp_bias"]:
        raise harness.HarnessError("the program's decoder has eps 1e-6 "
                                   "and no biases")
    return ArchConfig(
        name=name, family="dense", n_layers=cfg["num_hidden_layers"],
        d_model=cfg["hidden_size"], n_heads=cfg["num_attention_heads"],
        n_kv=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        vocab=cfg["vocab_size"], rope_theta=float(cfg["rope_theta"]),
        tie_embeddings=bool(cfg["tie_word_embeddings"]), act="swiglu",
        serve_kv_bits=cfg["engine"]["kv_bits"])


class _Counted:
    """A jitted engine program with a dispatch counter beside it."""

    def __init__(self, fn):
        self.fn, self.calls = fn, 0

    def __call__(self, *a, **k):
        self.calls += 1
        return self.fn(*a, **k)


def run(cell: harness.Cell) -> Outcome:
    from repro.serving import BucketShape, Engine

    cfg, mix, e = cell.config, cell.traffic, cell.config["engine"]
    act_bits = e["act_bits"]
    if cell.control and cfg["control"]["kind"] == "program":
        act_bits = cfg["control"]["act_bits"]
    phases: Dict[str, float] = {}
    t = harness.now()
    phases["start_s"] = t - cell.t_start
    arch = arch_config(cell.config_name, cfg)
    bucket = BucketShape(e["batch"], e["s_max"])
    params = weights.granite_params(cfg, cell.seed)
    jax.block_until_ready(params)
    phases["weights_s"] = harness.now() - t

    t = harness.now()
    engine = Engine(arch, params, compute=e["compute"],
                    weight_bits=e["weight_bits"], act_bits=act_bits,
                    plan_policy=e["plan_policy"], plan_cache=None,
                    buckets=(bucket,), prefill_chunk=e["prefill_chunk"],
                    wave_quantum=e["wave_quantum"], speculative=False,
                    queue_budget=2 * bucket.batch)
    jax.block_until_ready(engine.packed_params(bucket.batch))
    del params
    phases["pack_s"] = harness.now() - t
    t = harness.now()
    engine.warmup(bucket, inject=False)
    phases["warmup_s"] = harness.now() - t
    engine._dec = dec = _Counted(engine._dec)
    engine._pre = pre = _Counted(engine._pre)
    table = engine._states[bucket.key].sessions

    # -- the closed loop ------------------------------------------------
    reqs = traffic.closed_loop_requests(mix, bucket.batch, cfg["vocab_size"],
                                        cell.seed)
    nxt = [0] * bucket.batch                # next request per client
    info: Dict[int, tuple] = {}             # rid -> (client, prompt)
    slot_of: Dict[int, int] = {}            # rid -> KV slot it ran in
    toks: Dict[int, List[int]] = {}         # rid -> tokens served
    times: Dict[int, List[float]] = {}      # rid -> emission times

    def submit(client: int) -> None:
        # a client that has sent every round starts over (the engine
        # keeps no state between requests, so a repeat is new work)
        prompt, n_out = reqs[client][nxt[client] % len(reqs[client])]
        nxt[client] += 1
        with jax.profiler.TraceAnnotation("engine.submit"):
            rid = engine.submit(prompt, n_out)
        info[rid], toks[rid], times[rid] = (client, prompt), [], []

    def observe(t_now: float, comps) -> int:
        """Stamp the tokens the last step emitted; returns their count."""
        n = 0
        active = table.active()
        for slot, s in active:
            slot_of.setdefault(s.request.rid, slot)
        seen = [(s.request.rid, s.tokens) for _, s in active]
        seen += [(c.rid, c.tokens) for c in comps]
        for rid, got in seen:
            k = len(got) - len(toks[rid])
            if k > 0:
                toks[rid] = list(got)
                times[rid].extend([t_now] * k)
                n += k
        for c in comps:
            submit(info[c.rid][0])
        return n

    t = harness.now()
    for client in range(bucket.batch):
        submit(client)
    while any(not toks[rid] for rid in info):
        comps = engine.step()
        observe(harness.now(), comps)
    phases["prefill_s"] = harness.now() - t

    win: Dict = {}
    occupancy: List[float] = []
    steps: List[tuple] = []                 # (start, end, tokens) per step
    dec0, pre0 = dec.calls, pre.calls
    with cell.window(win):
        t0 = harness.now()
        t_prev = t0
        while t_prev - t0 < cell.seconds:
            with jax.profiler.TraceAnnotation("engine.step"):
                comps = engine.step()
            t_now = harness.now()
            steps.append((t_prev, t_now, observe(t_now, comps)))
            occupancy.append(len(table.active()) / bucket.batch)
            t_prev = t_now
        t1 = harness.now()
    window_s = t1 - t0

    emitted = [(rid, k, tt) for rid, ts in times.items()
               for k, tt in enumerate(ts) if t0 < tt <= t1]
    gaps = [ts[k] - ts[k - 1] for rid, ts in times.items()
            for k in range(1, len(ts)) if t0 < ts[k] <= t1]
    contexts = [len(info[rid][1]) + k for rid, k, _ in emitted]
    end_to_end = {"setup_s": t0 - cell.t_start,
                  "tokens_per_s": window_rate(steps, t0, cell.seconds)}
    step_s = sorted(b - a for a, b, _ in steps)
    # the tail rests on the iterations at least as long as it
    at_p95 = 0
    if gaps:
        p95 = nearest_rank(gaps, 95)
        end_to_end["itl_p95_ms"] = p95 * 1e3
        at_p95 = sum(1 for d in step_s if d >= p95)
    counters = {
        "iterations": len(step_s), "iterations_at_p95": at_p95,
        "step_ms": [1e3 * step_s[0], 1e3 * step_s[len(step_s) // 2],
                    1e3 * step_s[-1]],
        "window_s": window_s,
        "tokens": len(emitted), "gaps": len(gaps),
        "contexts": contexts, "occupancy": occupancy,
        "decode_calls": dec.calls - dec0, "prefill_calls": pre.calls - pre0,
        "batch": bucket.batch, "prefill_chunk": engine.prefill_chunk,
        **{k: v for k, v in win.items() if k != "summary"},
        "requests_finished": sum(1 for rid in info
                                 if rid in engine.outcomes),
        "setup_phases_s": phases,
    }
    mem_peak = harness.memory_peak_bytes()
    failed = sum(1 for o in engine.outcomes.values() if o["outcome"] != "ok")
    attempted = len(info)

    # -- correctness: the reference over a sample of served requests:
    # the longest, and one drawn from the seed per KV slot, so a fault
    # in any one slot shows
    served = [rid for rid in info if toks[rid]]
    pick = [max(served, key=lambda r: len(toks[r]))]
    for slot in range(bucket.batch):
        ran = [r for r in served if slot_of.get(r) == slot]
        if ran:
            pick.append(ran[traffic.sample_indices(len(ran), 1,
                                                   cell.seed + slot)[0]])
    pick = sorted(set(pick))
    seqs = [list(info[r][1]) + toks[r] for r in pick]
    del engine, table, dec, pre
    gc.collect()
    t = harness.now()
    checks, stats = compare(cfg, cell, seqs, [len(info[r][1]) for r in pick])
    counters.update(stats, check_s=harness.now() - t)
    counters["requests_compared"] = len(pick)
    return Outcome(end_to_end=end_to_end, counters=counters, checks=checks,
                   attempted=attempted, failed=failed,
                   memory_peak_bytes=mem_peak, summary=win.get("summary"))


def compare(cfg: Dict, cell: harness.Cell, seqs: List[List[int]],
            prompt_lens: List[int]):
    """Gaps by which each served token's reference logit lies below the
    reference's best, over every served token of ``seqs``: the widest,
    the mean, and the share of tokens that are not the reference's
    first choice."""
    e = cfg["engine"]
    tokens = reference.pad_sequences(seqs)
    targets = np.zeros_like(tokens)
    targets[:, :-1] = tokens[:, 1:]          # position j predicts j + 1
    served = np.zeros(tokens.shape, bool)
    for i, (q, p) in enumerate(zip(seqs, prompt_lens)):
        served[i, p - 1:len(q) - 1] = True
    ref_bits = e["act_bits"] if e["act_bits"] < 16 else None
    others = []
    if cell.control and cfg["control"]["kind"] == "reference":
        # the reference at the lower precision stands in for the
        # program: its own first choice at every served position
        others = [reference.decoder_pass(
            cfg, cell.seed, tokens, act_bits=cfg["control"]["act_bits"],
            targets=targets)[1]]
    best, _, at_target, at_other = reference.decoder_pass(
        cfg, cell.seed, tokens, act_bits=ref_bits, targets=targets,
        others=others)
    chosen = at_other[0] if others else at_target
    gaps = (best - chosen)[served]
    stats = {"max_logit_gap": float(gaps.max()),
             "mean_logit_gap": float(gaps.mean()),
             "off_first_choice": float((gaps > 0).mean())}
    # the configuration names the numbers it compares (each with a
    # limit set from sound and control readings); the others are
    # reported beside them
    limits = cfg["checks"]
    checks = [Check(k, stats[k], limits[k]) for k in stats if k in limits]
    checks.append(Check("tokens_compared", int(served.sum()),
                        limits["min_tokens_compared"], kind="min"))
    return checks, stats
