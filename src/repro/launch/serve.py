"""Serving launcher — a thin CLI over the online serving engine.

``--engine on`` (default) runs requests through
``repro.serving.Engine``: the continuous batcher coalesces them into
planner-bucketed batch shapes, each bucket warm-compiles once and
resolves its lane plans through the mixed-precision planner
(``plan_policy`` defaults to ``cache`` when a plan-cache file exists,
else ``auto``), and the metrics snapshot reports p50/p99 latency,
tokens/s and packed-multiply utilization.  ``--engine off`` keeps the
pre-engine fixed-shape loop (one synthetic batch, one shape) as the
comparison baseline.

``--packed-compute sdv`` runs every projection — 2-D kernels and
scanned layer stacks — on the SDV arithmetic datapath through the
``kernels/ops.packed_matmul`` dispatch and (unless ``--conv-datapath
float``) every SSM/Griffin short conv on the BSEG datapath;
``memory`` packs the weights in HBM only and lets XLA own the
dequant+matmul fusion.

  PYTHONPATH=src python -m repro.launch.serve --arch mamba2-130m --smoke \
      --packed-compute sdv
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np


def single_batch_loop(cfg, qparams, cache, prompts, new_tokens, *,
                      sync=None):
    """The ``--engine off`` loop: teacher-force one fixed batch of
    prompts, then greedy-decode ``new_tokens``.

    ``sync`` runs on every step's logits INSIDE the timed loop
    (default ``jax.block_until_ready``) — without it JAX's async
    dispatch lets the clock stop before the device finishes and the
    reported latency is understated (the same bug class fixed in
    ``kernelbench._t`` in PR 2; the serve smoke asserts the sync
    happens).  Returns (generated tokens [B, new_tokens], seconds).
    """
    from repro.models import decode_step
    if sync is None:
        sync = jax.block_until_ready
    b, plen = prompts.shape
    smax = plen + new_tokens
    dec = jax.jit(lambda p, c, t: decode_step(cfg, p, c, t))
    tok = prompts[:, :1]
    gen = []
    t0 = time.perf_counter()
    for i in range(smax - 1):
        logits, cache = dec(qparams, cache, tok)
        sync(logits)
        if i + 1 < plen:
            tok = prompts[:, i + 1:i + 2]
        else:
            tok = jnp.argmax(logits[:, -1:, :cfg.vocab],
                             axis=-1).astype(jnp.int32)
            gen.append(np.asarray(tok)[:, 0])
    dt = time.perf_counter() - t0
    return np.stack(gen, 1), dt


def _run_single_batch(cfg, args, params):
    from repro.models import BSEGConv, init_cache, serve_params, values, Rules
    rules = Rules(tp=None, fsdp=None, ep=None, batch=())
    qparams = serve_params(params, bits=args.weight_bits, min_size=1024,
                           compute=args.packed_compute,
                           act_bits=args.act_bits,
                           conv_bseg=(args.packed_compute == "sdv"
                                      and args.conv_datapath == "bseg"),
                           plan_policy=args.plan_policy or "default",
                           plan_cache=args.plan_cache)
    smax = args.prompt_len + args.new_tokens
    cache = values(init_cache(cfg, rules, args.batch, smax))
    kv_note = "int8" if "k_scale" in cache else "bf16"
    compute_note = (f"SDV W{args.weight_bits}A{args.act_bits} datapath"
                    f" (plans: {args.plan_policy or 'default'})"
                    if args.packed_compute == "sdv"
                    else f"packed W{args.weight_bits} memory")
    n_conv = sum(isinstance(leaf, BSEGConv)
                 for leaf in jax.tree_util.tree_leaves(
                     qparams, is_leaf=lambda v: isinstance(v, BSEGConv)))
    conv_note = (f", {n_conv} BSEG-packed "
                 f"W{min(args.weight_bits, 4)}A4 short convs"
                 if n_conv else "")
    print(f"{cfg.name}: {compute_note}{conv_note}, "
          f"{kv_note} KV cache, batch {args.batch} (single-batch loop)")

    rng = np.random.default_rng(0)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab, (args.batch, args.prompt_len)),
        dtype=jnp.int32)
    gen, dt = single_batch_loop(cfg, qparams, cache, prompts,
                                args.new_tokens)
    path_note = ("packed_matmul dispatch (ref route off-TPU)"
                 if args.packed_compute == "sdv"
                 else "interpret-free jnp path")
    print(f"{args.batch * (smax - 1) / dt:.1f} tok/s "
          f"({jax.default_backend()}, {path_note})")
    print("sample:", gen[0][:12])


def _run_engine(cfg, args, params):
    from repro.serving import Backpressure, BucketShape, Engine, FaultPlan

    s_maxes = ([int(s) for s in args.buckets.split(",") if s]
               if args.buckets else
               [args.prompt_len + args.new_tokens,
                2 * (args.prompt_len + args.new_tokens)])
    faults = None
    if args.chaos:
        faults = FaultPlan.chaos(args.chaos_seed)
    engine = Engine(cfg, params, compute=args.packed_compute,
                    weight_bits=args.weight_bits, act_bits=args.act_bits,
                    conv_datapath=args.conv_datapath,
                    plan_policy=args.plan_policy,
                    plan_cache=args.plan_cache,
                    buckets=tuple(BucketShape(args.batch, s)
                                  for s in s_maxes),
                    breaker_threshold=2 if args.chaos else 3,
                    breaker_cooldown_s=0.2 if args.chaos else 2.0,
                    speculative=args.speculative,
                    spec_k=args.spec_k,
                    draft_bits=args.draft_bits,
                    draft_act_bits=args.draft_act_bits,
                    faults=faults)
    spec_note = (f", speculative k={args.spec_k} "
                 f"(draft W{args.draft_bits}A{args.draft_act_bits})"
                 if args.speculative else "")
    print(f"{cfg.name}: engine, {args.packed_compute} compute, "
          f"plan policy {engine.plan_policy}, buckets "
          f"{[b.key for b in engine.buckets]}{spec_note}"
          + (f", chaos seed {args.chaos_seed}" if args.chaos else ""))

    rng = np.random.default_rng(0)
    n = args.requests or 2 * args.batch
    for _ in range(n):
        pl = int(rng.integers(max(1, args.prompt_len // 2),
                              args.prompt_len + 1))
        nt = int(rng.integers(max(1, args.new_tokens // 2),
                              args.new_tokens + 1))
        deadline = (engine.clock() + args.slo_ms / 1e3
                    if args.slo_ms else None)
        try:
            engine.submit(tuple(rng.integers(0, cfg.vocab, pl)), nt,
                          deadline=deadline)
        except Backpressure:
            pass
    comps = engine.drain()
    snap = engine.metrics.snapshot()
    print(f"{snap['requests_completed']} done "
          f"({snap['requests_rejected']} rejected, "
          f"{snap['requests_shed']} shed), "
          f"{snap['tokens_per_s']:.1f} tok/s, "
          f"p50 {snap['latency']['p50_ms']:.1f} ms, "
          f"p99 {snap['latency']['p99_ms']:.1f} ms, "
          f"{snap['waves']['count']} waves")
    if args.chaos:
        f = snap["faults"]
        print(f"chaos: {f['wave_failures']} wave failures "
              f"{f['kinds']}, {f['quarantines']} quarantines, "
              f"{f['recoveries']} recoveries, {f['rerouted']} rerouted, "
              f"{f['fallback_waves']} fallback waves; "
              f"health {engine.bucket_health()}")
    for key, util in engine.plan_report().items():
        print(f"bucket {key}: {util['kernel_routed_layers']}/"
              f"{util['packed_layers']} packed layers on kernel routes, "
              f"density {util['density_achieved']:.2f} MACs/multiply")
    if args.speculative:
        sp = snap["speculative"]
        print(f"speculative: {sp['rounds']} rounds, "
              f"mean accepted {sp['mean_accepted']:.2f}, "
              f"tok/target-wave {sp['tokens_per_target_wave']:.2f}, "
              f"acceptance hist {sp['acceptance_hist']}")
        for key, rep in engine.spec_report().items():
            denser = sum(1 for l in rep["layers"] if l["draft_denser"])
            print(f"bucket {key}: spec_on={rep['spec_on']}, "
                  f"{denser}/{len(rep['layers'])} draft layers "
                  f"strictly denser")
    if comps:
        print("sample:", list(comps[0].tokens)[:12])
    failed = [o["detail"] for o in engine.outcomes.values()
              if o["outcome"] == "failed"]
    if failed and not args.chaos:
        # outside a chaos run a failed request means a broken device
        # path (the engine degrades instead of dying): exit non-zero
        raise SystemExit(f"{len(failed)} requests failed: {failed[0]}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="gemma-2b")
    # BooleanOptionalAction so --no-smoke actually disables it (the old
    # store_true + default=True flag could never be turned off)
    ap.add_argument("--smoke", action=argparse.BooleanOptionalAction,
                    default=True,
                    help="reduced config (--no-smoke runs full size)")
    ap.add_argument("--engine", choices=("on", "off"), default="on",
                    help="on: the continuous-batching serving engine; "
                         "off: the pre-engine single-batch loop")
    ap.add_argument("--batch", type=int, default=8,
                    help="batch (single-batch loop) / bucket width "
                         "(engine KV slots per wave)")
    ap.add_argument("--requests", type=int, default=None,
                    help="engine: requests to submit (default 2*batch)")
    ap.add_argument("--buckets", default=None,
                    help="engine: comma-separated bucket s_max ladder "
                         "(default: prompt+new and 2x)")
    ap.add_argument("--slo-ms", type=float, default=None,
                    help="engine: per-request deadline (submit + slo)")
    ap.add_argument("--chaos", action="store_true",
                    help="engine: inject the seeded all-classes fault "
                         "schedule (FaultPlan.chaos) and print the "
                         "health/fault summary")
    ap.add_argument("--chaos-seed", type=int, default=0)
    ap.add_argument("--speculative", action="store_true",
                    help="engine: self-speculation draft + single-wave "
                         "verification (greedy-exact, DESIGN.md §5.2)")
    ap.add_argument("--spec-k", type=int, default=3,
                    help="drafted tokens per verification wave")
    ap.add_argument("--draft-bits", type=int, default=4,
                    help="draft weight bits")
    ap.add_argument("--draft-act-bits", type=int, default=4,
                    help="draft activation bits (the density knob)")
    ap.add_argument("--prompt-len", type=int, default=24)
    ap.add_argument("--new-tokens", type=int, default=24)
    ap.add_argument("--weight-bits", type=int, default=4)
    ap.add_argument("--packed-compute", choices=("memory", "sdv"),
                    default="sdv")
    ap.add_argument("--act-bits", type=int, default=8,
                    help="activation width on the SDV datapath")
    ap.add_argument("--conv-datapath", choices=("bseg", "float"),
                    default="bseg",
                    help="short-conv execution under --packed-compute "
                         "sdv: BSEG packed datapath or float math")
    ap.add_argument("--plan-policy", choices=("default", "auto", "cache"),
                    default=None,
                    help="lane-plan selection; engine default: cache "
                         "when a plan-cache file exists, else auto; "
                         "single-batch default: the uniform plans")
    ap.add_argument("--plan-cache", default=None,
                    help="plan-cache JSON path for --plan-policy cache")
    args = ap.parse_args()

    from repro.configs.registry import get_arch
    from repro.models import init_params, values, Rules

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    rules = Rules(tp=None, fsdp=None, ep=None, batch=())
    params = values(init_params(cfg, rules, jax.random.PRNGKey(0)))
    if args.engine == "on":
        _run_engine(cfg, args, params)
    else:
        _run_single_batch(cfg, args, params)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
