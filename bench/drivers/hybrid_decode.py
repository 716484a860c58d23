"""Driver: the granite-4.0-h-small stage (Mamba2 and attention layers
over dropless experts) served by ``repro.serving.Engine`` under the
closed loop of ``drivers/decode.py``: one client per slot of a single
bucket, every first request served to its first token in set-up, then
each ``Engine.step()`` one iteration of the window, joiners prefilling
in chunks beside the others' decode.

What differs from ``drivers/decode.py``:

* the program must support the configuration (the moe_hybrid family,
  per-slot state with the advance mask and chunked prefill); it is
  asked before any weight is made, so a program without it fails at
  once;
* the weights are made and packed one layer at a time
  (``repro.models.LayerStream``, ``weights_granite_hybrid.py``): the
  bf16 copy of a whole period never exists;
* the engine's MoE counters over the window are reported beside the
  loop's;
* the check compares logits, not only the served tokens' ranks: with
  random weights and the tied head, the embedding multiplier makes
  every position's first choice its own token by a margin no rounding
  moves, so the program's and a control's tokens agree.  So each
  decode call also keeps, per slot, the logit of its greedy choice and
  the logits at ``PROBE_IDS`` vocabulary entries drawn from the seed
  (a small gather on the device, read back after the window), and the
  plain reference (``reference_granite_hybrid.py``) gives the same
  entries at the same positions; the mean absolute difference is
  compared with the configuration's limit.
"""
from __future__ import annotations

import gc
from typing import Dict, List

import jax
import numpy as np

import harness
import reference
import reference_granite_hybrid
import traffic
import weights_granite_hybrid
from drivers.decode import _Counted, nearest_rank, window_rate
from harness import Check, Outcome

#: vocabulary entries whose logits the check compares at every served
#: position, beside the served token's own
PROBE_IDS = 128

#: EngineMetrics counters of the dropless MoE layers
MOE_COUNTERS = ("moe_calls", "moe_rows", "moe_peak_rows",
                "moe_experts_touched", "moe_load_peak")


def arch_config(name: str, cfg: Dict):
    """The program's ``ArchConfig`` for the configuration file; refuses
    a program that cannot serve it and settings it cannot run."""
    want = {"model_type": "granitemoehybrid", "hidden_act": "silu",
            "normalization_function": "rmsnorm",
            "position_embedding_type": "nope", "attention_bias": False,
            "mamba_conv_bias": True, "mamba_proj_bias": False,
            "mamba_d_conv": 4, "mamba_chunk_size": 256}
    for k, v in want.items():
        if cfg[k] != v:
            raise harness.HarnessError(f"{k} {cfg[k]!r}: the program runs "
                                       f"{v!r}")
    d = cfg["hidden_size"]
    if cfg["mamba_d_head"] * cfg["mamba_n_heads"] != cfg["mamba_expand"] * d:
        raise harness.HarnessError("mamba heads do not tile d_inner")
    try:
        from repro.configs.base import ArchConfig
        from repro.models import slot_state
        arch = ArchConfig(
            name=name, family="moe_hybrid",
            n_layers=cfg["num_hidden_layers"], d_model=d,
            n_heads=cfg["num_attention_heads"],
            n_kv=cfg["num_key_value_heads"],
            head_dim=d // cfg["num_attention_heads"],
            d_ff=cfg["intermediate_size"], vocab=cfg["vocab_size"],
            n_experts=cfg["num_local_experts"],
            top_k=cfg["num_experts_per_tok"], shared_expert=True,
            shared_d_ff=cfg["shared_intermediate_size"],
            d_inner=cfg["mamba_expand"] * d,
            ssm_state=cfg["mamba_d_state"], ssm_heads=cfg["mamba_n_heads"],
            ssm_groups=cfg["mamba_n_groups"],
            layer_types=tuple(cfg["layer_types"]), use_rope=False,
            attn_scale=float(cfg["attention_multiplier"]),
            rms_eps=float(cfg["rms_norm_eps"]),
            embedding_multiplier=float(cfg["embedding_multiplier"]),
            residual_multiplier=float(cfg["residual_multiplier"]),
            logits_scaling=float(cfg["logits_scaling"]),
            tie_embeddings=bool(cfg["tie_word_embeddings"]), act="swiglu",
            serve_kv_bits=cfg["engine"]["kv_bits"])
    except (ImportError, TypeError) as e:
        raise harness.HarnessError(f"the program has no moe_hybrid model "
                                   f"with per-slot state: {e}") from None
    if not slot_state(arch):
        raise harness.HarnessError("the program replays this model's "
                                   "prompts token by token")
    return arch


class _Logits:
    """A jitted decode program that also keeps, per call and slot, the
    logit of the greedy choice and the logits at ``ids`` ([B, 1 + m],
    on the device until the window has closed)."""

    def __init__(self, fn, ids: np.ndarray, vocab: int):
        import jax.numpy as jnp
        self.fn, self.rows = fn, []
        idx = jnp.asarray(ids, jnp.int32)
        self._take = jax.jit(lambda lg: jnp.concatenate(
            [lg[:, -1, :vocab].max(-1, keepdims=True), lg[:, -1, idx]],
            -1))

    def __call__(self, *a, **k):
        out = self.fn(*a, **k)
        self.rows.append(self._take(out[0]))
        return out


def probe_ids(cfg: Dict, seed: int) -> np.ndarray:
    return np.asarray(traffic.sample_indices(cfg["vocab_size"], PROBE_IDS,
                                             seed + 17), np.int32)


def run(cell: harness.Cell) -> Outcome:
    cfg, mix, e = cell.config, cell.traffic, cell.config["engine"]
    phases: Dict[str, float] = {}
    t = harness.now()
    phases["start_s"] = t - cell.t_start
    arch = arch_config(cell.config_name, cfg)
    from repro.models import LayerStream
    from repro.serving import BucketShape, Engine
    bucket = BucketShape(e["batch"], e["s_max"])
    period = arch.layer_period
    stream = LayerStream(
        shared=weights_granite_hybrid.shared(cfg, cell.seed),
        names=tuple(f"l{j}" for j in range(len(period))),
        depth=arch.n_layers // len(period),
        make=lambda name, i: weights_granite_hybrid.layer(
            cfg, cell.seed, i * len(period) + int(name[1:])))
    jax.block_until_ready(stream.shared)
    phases["weights_s"] = harness.now() - t

    t = harness.now()
    engine = Engine(arch, stream, compute=e["compute"],
                    weight_bits=e["weight_bits"], act_bits=e["act_bits"],
                    plan_policy=e["plan_policy"], plan_cache=None,
                    buckets=(bucket,), prefill_chunk=e["prefill_chunk"],
                    wave_quantum=e["wave_quantum"], speculative=False,
                    queue_budget=2 * bucket.batch)
    jax.block_until_ready(engine.packed_params(bucket.batch))
    del stream
    phases["pack_s"] = harness.now() - t
    t = harness.now()
    engine.warmup(bucket, inject=False)
    phases["warmup_s"] = harness.now() - t
    ids = probe_ids(cfg, cell.seed)
    logits = _Logits(engine._dec, ids, cfg["vocab_size"])
    engine._dec = dec = _Counted(logits)
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
    rows: Dict[int, List[tuple]] = {}       # rid -> (decode call, slot)

    def submit(client: int) -> None:
        prompt, n_out = reqs[client][nxt[client] % len(reqs[client])]
        nxt[client] += 1
        with jax.profiler.TraceAnnotation("engine.submit"):
            rid = engine.submit(prompt, n_out)
        info[rid], toks[rid], times[rid] = (client, prompt), [], []
        rows[rid] = []

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
                # one token a decode call: the last call's row at the
                # request's slot holds the logits it came from
                assert k == 1, (rid, k)
                toks[rid] = list(got)
                times[rid].extend([t_now] * k)
                rows[rid].append((len(logits.rows) - 1, slot_of[rid]))
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
    moe0 = {k: getattr(engine.metrics, k) for k in MOE_COUNTERS}
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
        **{k: getattr(engine.metrics, k) - v for k, v in moe0.items()},
        **{k: v for k, v in win.items() if k != "summary"},
        "requests_finished": sum(1 for rid in info
                                 if rid in engine.outcomes),
        "setup_phases_s": phases,
    }
    mem_peak = harness.memory_peak_bytes()
    failed = sum(1 for o in engine.outcomes.values() if o["outcome"] != "ok")
    attempted = len(info)

    # -- correctness: the reference over a sample of served requests:
    # the longest, and one drawn from the seed per slot
    served = [rid for rid in info if toks[rid]]
    pick = [max(served, key=lambda r: len(toks[r]))]
    for slot in range(bucket.batch):
        ran = [r for r in served if slot_of.get(r) == slot]
        if ran:
            pick.append(ran[traffic.sample_indices(len(ran), 1,
                                                   cell.seed + slot)[0]])
    pick = sorted(set(pick))
    seqs = [list(info[r][1]) + toks[r] for r in pick]
    kept = jax.device_get(logits.rows)
    served_logits = np.concatenate([np.stack([kept[i][slot]
                                              for i, slot in rows[r]])
                                     for r in pick])
    del engine, table, dec, pre, logits, kept
    gc.collect()
    t = harness.now()
    checks, stats = compare(cfg, cell, seqs, [len(info[r][1]) for r in pick],
                            served_logits, ids)
    counters.update(stats, check_s=harness.now() - t)
    counters["requests_compared"] = len(pick)
    return Outcome(end_to_end=end_to_end, counters=counters, checks=checks,
                   attempted=attempted, failed=failed,
                   memory_peak_bytes=mem_peak, summary=win.get("summary"))


def compare(cfg: Dict, cell: harness.Cell, seqs: List[List[int]],
            prompt_lens: List[int], served_logits: np.ndarray,
            ids: np.ndarray):
    """The program's logits against the plain reference's at every
    served token of ``seqs``: ``served_logits`` [tokens, 1 + m] holds,
    in order, each served token's logit and the logits at ``ids``.
    Compared: the mean absolute difference over those entries.  Beside
    it: the widest and the mean gap by which a served token's reference
    logit lies below the reference's best, and the share of tokens that
    are not the reference's first choice.  The control puts the
    reference at the control's activation width in the program's
    place: its logits at the same entries, and its own first choices
    for the gaps."""
    tokens = reference.pad_sequences(seqs)
    lengths = [len(q) for q in seqs]
    targets = np.zeros_like(tokens)
    targets[:, :-1] = tokens[:, 1:]          # position j predicts j + 1
    served = np.zeros(tokens.shape, bool)
    for i, (q, p) in enumerate(zip(seqs, prompt_lens)):
        served[i, p - 1:len(q) - 1] = True
    others, got = [], served_logits
    if cell.control and cfg["control"]["kind"] == "reference":
        _, first, at_t, _, at_p = reference_granite_hybrid.hybrid_pass(
            cfg, cell.seed, tokens, lengths,
            act_bits=cfg["control"]["act_bits"], targets=targets, probe=ids)
        others = [first]
        got = np.concatenate([at_t[..., None], at_p], -1)[served]
    best, _, at_target, at_other, at_probe = \
        reference_granite_hybrid.hybrid_pass(
            cfg, cell.seed, tokens, lengths, act_bits=None, targets=targets,
            others=others, probe=ids)
    want = np.concatenate([at_target[..., None], at_probe], -1)[served]
    err = np.abs(got - want)
    chosen = at_other[0] if others else at_target
    gaps = (best - chosen)[served]
    stats = {"mean_logit_error": float(err.mean()),
             "max_logit_error": float(err.max()),
             "max_logit_gap": float(gaps.max()),
             "mean_logit_gap": float(gaps.mean()),
             "off_first_choice": float((gaps > 0).mean())}
    limits = cfg["checks"]
    checks = [Check(k, stats[k], limits[k]) for k in stats if k in limits]
    checks.append(Check("tokens_compared", int(served.sum()),
                        limits["min_tokens_compared"], kind="min"))
    return checks, stats
