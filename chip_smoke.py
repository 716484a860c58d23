#!/usr/bin/env python3
"""Bring-up check on the TPU: the main paths once, at real widths.

One chip (no arguments) — three phases in this one process:

  a. serving: gemma-2b at its published widths and full depth (18
     layers, d_model 2048, d_ff 16384, vocab 256000; random weights
     from ``--seed``) through ``repro.serving.Engine`` — SDV compute,
     W4A8, plan policy ``auto``, speculative decoding off, two batch-8
     buckets, 16 seeded requests, drained;
  b. kernel route vs ref route on the engine's packed tree: every
     packed layer's integer outputs, Pallas kernel against the jnp ref
     decode, bitwise, at the decode (GEMV) and GEMM row counts; then
     one ``prefill_slot`` chunk and one decode step of the whole model
     on both routes — equal greedy tokens, the largest logit difference
     printed;
  c. UltraNet at the paper's 416x416 frame: ``mode="bseg"`` bitwise
     equal to ``mode="ref"``.

Four chips (``--chips 4``) — this phase only:

  d. the training launcher's state (``launch.train.setup_training``):
     tinyllama-1.1b at published widths, FSDP over a 4x1 data mesh, a
     few steps with the loss printed; then the SDV-packed int8 gradient
     all-reduce, packed bitwise equal to unpacked on the same mesh.

A request that is not ``ok``, a failed, quarantined or fallback wave, a
packed layer on the ref route, a mismatch or an exception fails the run
with a non-zero exit.  Without a TPU it exits non-zero at once; it never
falls back to the CPU.  One line per phase is printed (times labelled
informational are host-clock readings, not benchmark results), and the
last line of standard output is

  {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}

Usage: ``python chip_smoke.py [--chips 4] [--seed 0]``.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time
import traceback
import warnings

REPO = os.path.dirname(os.path.abspath(__file__))

SERVE_ARCH = "gemma-2b"
BUCKETS = ((8, 64), (8, 128))           # (batch, s_max)
N_REQUESTS = 16
LENGTHS = (8, 40)                       # prompt / answer tokens, inclusive
TRAIN_ARCH = "tinyllama-1.1b"
TRAIN_STEPS = 4
TRAIN_SEQ = 512
TRAIN_BATCH = 8
ULTRANET_FRAME = 416


class CompileClock:
    """Accumulates JAX's own compile-event durations (tracing, lowering
    and the backend compile) so each phase can report its compile
    seconds apart from its run time."""

    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        self.seconds = 0.0
        self.backend_compiles = 0

    def __call__(self, event: str, duration: float, **_) -> None:
        if event in self.EVENTS:
            self.seconds += duration
            self.backend_compiles += event == self.EVENTS[2]

    def mark(self):
        return self.seconds, self.backend_compiles

    def since(self, mark):
        return self.seconds - mark[0], self.backend_compiles - mark[1]


@dataclasses.dataclass
class Context:
    seed: int
    clock: CompileClock
    engine: object = None


def _peak_bytes(key: str = "peak_bytes_in_use") -> int:
    import jax
    stats = jax.devices()[0].memory_stats() or {}
    return int(stats.get(key, -1))


# ---------------------------------------------------------------------------
# a. serving engine
# ---------------------------------------------------------------------------

def phase_serving(ctx: Context):
    import jax
    import numpy as np
    from repro.configs.registry import get_arch
    from repro.models import Rules, init_params, values
    from repro.serving import BucketShape, Engine

    cfg = get_arch(SERVE_ARCH)
    buckets = tuple(BucketShape(b, s) for b, s in BUCKETS)
    t0 = time.perf_counter()
    params = values(init_params(cfg, Rules(tp=None, fsdp=None, ep=None,
                                           batch=()),
                                jax.random.PRNGKey(ctx.seed)))
    jax.block_until_ready(params)
    init_s = time.perf_counter() - t0
    peak_init = _peak_bytes()
    mark = ctx.clock.mark()
    t0 = time.perf_counter()
    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        engine = Engine(cfg, params, compute="sdv", weight_bits=4,
                        act_bits=8, plan_policy="auto", buckets=buckets,
                        speculative=False)
        ctx.engine = engine
        for b in buckets:                   # compile errors raise here
            engine.warmup(b, inject=False)
    warm_s = time.perf_counter() - t0
    compile_s, n_compiles = ctx.clock.since(mark)
    del params                              # the engine holds its own

    rng = np.random.default_rng(ctx.seed)
    lo, hi = LENGTHS
    for _ in range(N_REQUESTS):
        plen = int(rng.integers(lo, hi + 1))
        engine.submit(tuple(int(t) for t in
                            rng.integers(0, cfg.vocab, plen)),
                      int(rng.integers(lo, hi + 1)))
    mark = ctx.clock.mark()
    t0 = time.perf_counter()
    comps = engine.drain()
    drain_s = time.perf_counter() - t0
    _, drain_compiles = ctx.clock.since(mark)

    snap = engine.metrics.snapshot()
    report = engine.plan_report()
    problems = []
    bad = {rid: o for rid, o in engine.outcomes.items()
           if o["outcome"] != "ok"}
    if len(engine.outcomes) != N_REQUESTS or bad:
        problems.append(f"{len(engine.outcomes)}/{N_REQUESTS} outcomes, "
                        f"not ok: {bad}")
    faults = snap["faults"]
    for key in ("wave_failures", "quarantines", "fallback_waves",
                "rerouted"):
        if faults[key]:
            problems.append(f"{key}={faults[key]} {faults['kinds']}")
    for key in ("requests_failed", "requests_shed", "requests_rejected"):
        if snap[key]:
            problems.append(f"{key}={snap[key]}")
    if snap["speculative"]["degraded_buckets"]:
        problems.append("speculative decoding degraded")
    routes = {k: f"{u['kernel_routed_layers']}/{u['packed_layers']}"
              for k, u in report.items()}
    if len(report) != len(buckets) or any(
            u["packed_layers"] == 0
            or u["kernel_routed_layers"] != u["packed_layers"]
            for u in report.values()):
        problems.append(f"packed layers off the kernel routes: {routes}")
    ref_warnings = [str(w.message) for w in caught
                    if "ref route" in str(w.message)]
    if ref_warnings:
        problems.append(f"ref-route warnings: {ref_warnings}")
    tokens = sum(len(c.tokens) for c in comps)
    line = {
        "arch": cfg.name, "layers": cfg.n_layers, "d_model": cfg.d_model,
        "d_ff": cfg.d_ff, "vocab": cfg.vocab,
        "buckets": [b.key for b in buckets],
        "requests_ok": sum(o["outcome"] == "ok"
                           for o in engine.outcomes.values()),
        "requests": N_REQUESTS, "tokens_out": tokens,
        "init_s": init_s, "warmup_s": warm_s, "compile_s": compile_s,
        "compiles": n_compiles, "compiles_while_serving": drain_compiles,
        "drain_s_informational": drain_s,
        "tokens_per_s_informational": tokens / drain_s,
        "waves": snap["waves"]["count"],
        "wave_failures": faults["wave_failures"],
        "quarantines": faults["quarantines"],
        "fallback_waves": faults["fallback_waves"],
        "kernel_routed_layers": routes,
        "peak_bytes_after_init": peak_init,
        "peak_bytes_in_use": _peak_bytes(),
        "bytes_limit": _peak_bytes("bytes_limit"),
    }
    return line, problems


# ---------------------------------------------------------------------------
# b. kernel route vs ref route
# ---------------------------------------------------------------------------

def phase_routes(ctx: Context):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels import bseg_common, ops
    from repro.models import (Rules, SDVLinear, decode_step, init_cache,
                              prefill_slot, values)
    from repro.models.quantized import is_packed

    @functools.partial(jax.jit, static_argnames=("plan", "m", "mode"))
    def layer_out(words, x, layer, *, plan, m, mode):
        return ops.packed_matmul(x, words[layer], plan=plan, m=m,
                                 mode=mode)

    engine = ctx.engine
    if engine is None:
        raise RuntimeError("phase a built no engine")
    cfg = engine.cfg
    batch, s_max = BUCKETS[0]
    qp = engine.packed_params(batch)
    ref_qp = jax.tree.map(
        lambda l: dataclasses.replace(l, use_kernel=False)
        if isinstance(l, SDVLinear) else l, qp, is_leaf=is_packed)
    rng = np.random.default_rng(ctx.seed + 1)
    problems = []
    mark = ctx.clock.mark()

    # -- every packed layer's integer outputs, kernel vs ref, bitwise --
    leaves = [(jax.tree_util.keystr(path), leaf) for path, leaf in
              jax.tree_util.tree_flatten_with_path(qp, is_leaf=is_packed)[0]
              if isinstance(leaf, SDVLinear)]
    checked, kernel_routes = 0, set()
    for name, leaf in leaves:
        plan = leaf.plan
        # per-layer words are [K, G] (+ a leading (2,) limb-plane axis on
        # wide plans); scanned stacks add a leading layer axis
        base = 2 + (bseg_common.sdv_word_spec(plan).limbs == 2)
        words = leaf.words if leaf.words.ndim == base + 1 \
            else leaf.words[None]
        depth, d_in = words.shape[0], words.shape[-2]
        amp = (1 << (plan.w_b - 1)) - 1
        for layer in sorted({0, depth // 2, depth - 1}):
            for rows in (batch, 128):
                route = ops.select_packed_route(rows, plan=plan)
                kernel_routes.add(route)
                if route == "ref":
                    problems.append(f"{name} rows={rows}: ref route")
                    continue
                x = jnp.asarray(rng.integers(-amp, amp + 1, (rows, d_in)),
                                jnp.int32)
                yk = layer_out(words, x, layer, plan=plan, m=leaf.d_out,
                                mode="auto")
                yr = layer_out(words, x, layer, plan=plan, m=leaf.d_out,
                                mode="ref")
                if not np.array_equal(np.asarray(yk), np.asarray(yr)):
                    problems.append(f"{name}[{layer}] rows={rows}: kernel "
                                    f"!= ref")
                checked += 1

    # -- one prefill_slot chunk and one decode step, both routes --------
    rules = Rules(tp=None, fsdp=None, ep=None, batch=())
    cache0 = values(init_cache(cfg, rules, batch, s_max))
    chunk = engine.prefill_chunk
    ptoks = jnp.asarray(rng.integers(0, cfg.vocab, (1, chunk)), jnp.int32)
    dtoks = jnp.asarray(rng.integers(0, cfg.vocab, (batch, 1)), jnp.int32)
    pre = jax.jit(lambda p, c, t: prefill_slot(
        cfg, p, c, 0, t, jnp.full((1,), chunk, jnp.int32)))
    dec = jax.jit(lambda p, c, t: decode_step(cfg, p, c, t))
    out = {}
    for route, tree in (("kernel", qp), ("ref", ref_qp)):
        cache = pre(tree, cache0, ptoks)
        logits, cache = dec(tree, cache, dtoks)
        out[route] = (jax.device_get(cache),
                      np.asarray(logits[:, -1, :cfg.vocab], np.float32))
    (ck, lk), (cr, lr) = out["kernel"], out["ref"]
    # the packed layers' integers agree (above); the float ops between
    # them may fuse differently around a custom call than around the
    # ref decode, so the int8 KV entries are counted, not required equal
    kv = [(np.asarray(a), np.asarray(b))
          for a, b in zip(jax.tree.leaves(ck), jax.tree.leaves(cr))
          if np.asarray(a).dtype == np.int8]
    kv_equal = sum(int((a == b).sum()) for a, b in kv) \
        / max(sum(a.size for a, _ in kv), 1)
    tok_k, tok_r = lk.argmax(-1), lr.argmax(-1)
    if not np.array_equal(tok_k, tok_r):
        problems.append(f"greedy tokens differ: {tok_k} vs {tok_r}")
    if not np.isfinite(lk).all():
        problems.append("non-finite logits on the kernel route")
    compile_s, n_compiles = ctx.clock.since(mark)
    line = {
        "layer_checks_bitwise": checked,
        "packed_leaves": len(leaves),
        "routes": sorted(kernel_routes),
        "kv_int8_equal_fraction": kv_equal,
        "greedy_tokens_equal": bool(np.array_equal(tok_k, tok_r)),
        "max_abs_logit_diff": float(np.abs(lk - lr).max()),
        "max_abs_logit": float(np.abs(lr).max()),
        "compile_s": compile_s, "compiles": n_compiles,
        "peak_bytes_in_use": _peak_bytes(),
    }
    return line, problems


# ---------------------------------------------------------------------------
# c. UltraNet at the 416 frame
# ---------------------------------------------------------------------------

def phase_ultranet(ctx: Context):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.models import ultranet as U

    params = U.init_ultranet(ctx.seed)
    rng = np.random.default_rng(ctx.seed + 2)
    img = jnp.asarray(rng.integers(0, 1 << U.A_BITS,
                                   (1, ULTRANET_FRAME, ULTRANET_FRAME, 3)),
                      jnp.int32)
    routes = U.ultranet_conv_routes(ULTRANET_FRAME, ULTRANET_FRAME)
    problems = [f"conv {i} on the ref route" for i, r in enumerate(routes)
                if r == "ref"]
    fwd = {mode: jax.jit(functools.partial(U.ultranet_forward, params,
                                           mode=mode))
           for mode in ("bseg", "ref")}
    mark = ctx.clock.mark()
    ys = {mode: np.asarray(jax.block_until_ready(f(img)))
          for mode, f in fwd.items()}
    compile_s, n_compiles = ctx.clock.since(mark)
    frame_ms = {}
    for mode, f in fwd.items():
        ts = []
        for _ in range(3):
            t0 = time.perf_counter()
            jax.block_until_ready(f(img))
            ts.append(time.perf_counter() - t0)
        frame_ms[mode] = 1e3 * min(ts)
    want = (1, ULTRANET_FRAME // 16, ULTRANET_FRAME // 16, U.HEAD_CHANNELS)
    if ys["bseg"].shape != want:
        problems.append(f"head shape {ys['bseg'].shape} != {want}")
    equal = bool(np.array_equal(ys["bseg"], ys["ref"]))
    if not equal:
        problems.append("UltraNet bseg != ref")
    line = {
        "frame": ULTRANET_FRAME, "routes": routes,
        "bseg_equals_ref_bitwise": equal,
        "compile_s": compile_s, "compiles": n_compiles,
        "frame_ms_informational": frame_ms,
        "peak_bytes_in_use": _peak_bytes(),
    }
    return line, problems


# ---------------------------------------------------------------------------
# d. four chips: the training launcher + the packed gradient all-reduce
# ---------------------------------------------------------------------------

def phase_train(ctx: Context, cfg=None, n_dev: int = 4):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as PS
    from repro.configs.registry import get_arch
    from repro.launch.mesh import make_mesh
    from repro.launch.train import setup_training
    from repro.models import shard_ctx
    from repro.train import loop
    from repro.train.grad_compress import compressed_allreduce

    cfg = cfg or get_arch(TRAIN_ARCH)
    devices = jax.devices()[:n_dev]
    mesh = make_mesh((n_dev, 1), ("data", "model"), devices=devices)
    problems = []
    mark = ctx.clock.mark()
    rules, ocfg, pv, opt, data, place_batch = setup_training(
        cfg, mesh, steps=TRAIN_STEPS, seq=TRAIN_SEQ,
        global_batch=TRAIN_BATCH, seed=ctx.seed)

    # placement, leaf by leaf: every weight and optimizer array (the
    # step counter aside) has a shard on every device, and every large
    # one is split (FSDP), not copied whole or left on the first device
    want = set(devices)
    leaves = jax.tree.leaves((pv, opt))
    off_mesh = [a.shape for a in leaves if a.ndim
                and {s.device for s in a.addressable_shards} != want]
    large = [a for a in leaves if a.size >= 1 << 20]
    whole = [a.shape for a in large
             if a.addressable_shards[0].data.size == a.size]
    big = max(jax.tree.leaves(pv), key=lambda a: a.size)
    shard_shape = big.addressable_shards[0].data.shape
    tok = place_batch(data.batch_at(0))["tokens"]
    if off_mesh:
        problems.append(f"{len(off_mesh)} state leaves not on all "
                        f"{n_dev} devices: {off_mesh[:4]}")
    if whole:
        problems.append(f"{len(whole)} of {len(large)} large state leaves "
                        f"unsharded: {whole[:4]}")
    if {s.device for s in tok.addressable_shards} != want \
            or tok.addressable_shards[0].data.shape[0] \
            != tok.shape[0] // n_dev:
        problems.append("batch is not split over the data axis")

    losses = []
    t0 = time.perf_counter()
    with mesh:
        with shard_ctx.use_rules(rules):
            loop.run_training(
                cfg, ocfg, pv, opt, data, steps=TRAIN_STEPS,
                microbatches=2, place_batch=place_batch,
                on_step=lambda s, p, o, m, dt, mon:
                    losses.append(float(m["loss"])))
    train_s = time.perf_counter() - t0
    if len(losses) != TRAIN_STEPS or not np.isfinite(losses).all():
        problems.append(f"losses {losses}")

    rng = np.random.default_rng(ctx.seed + 3)
    g = jnp.asarray(rng.standard_normal((n_dev, 1 << 20)) * 1e-3,
                    jnp.float32)
    grads = {"w": jax.device_put(g, NamedSharding(mesh, PS("data")))}
    errs = {"w": jnp.zeros_like(grads["w"])}
    gh_p, e_p = compressed_allreduce(grads, errs, mesh, axis="data",
                                     pack_words=True)
    gh_u, e_u = compressed_allreduce(grads, errs, mesh, axis="data",
                                     pack_words=False)
    bits = lambda a: np.asarray(a).view(np.uint32)      # noqa: E731
    ar_equal = bool(np.array_equal(bits(gh_p["w"]), bits(gh_u["w"]))
                    and np.array_equal(bits(e_p["w"]), bits(e_u["w"])))
    if not ar_equal:
        problems.append("packed all-reduce != unpacked")
    compile_s, n_compiles = ctx.clock.since(mark)
    line = {
        "arch": cfg.name, "mesh": dict(mesh.shape), "fsdp": cfg.fsdp,
        "seq": TRAIN_SEQ, "global_batch": TRAIN_BATCH,
        "state_leaves": len(leaves),
        "large_leaves_sharded": len(large) - len(whole),
        "large_leaves": len(large),
        "largest_weight": list(big.shape),
        "its_shard": list(shard_shape),
        "losses": losses,
        "train_s_informational": train_s,
        "packed_allreduce_bitwise_equal": ar_equal,
        "compile_s": compile_s, "compiles": n_compiles,
        "peak_bytes_in_use_per_device": [
            int((d.memory_stats() or {}).get("peak_bytes_in_use", -1))
            for d in devices],
    }
    return line, problems


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip training phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform "
              f"{devices[0].platform!r}); nothing was run", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} TPU "
              f"devices, found {len(devices)}", file=sys.stderr)
        return 2
    sys.path.insert(0, os.path.join(REPO, "src"))
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    clock = CompileClock()
    jax.monitoring.register_event_duration_secs_listener(clock)
    ctx = Context(seed=args.seed, clock=clock)

    phases = ([("d_train_4chip", phase_train)] if args.chips == 4 else
              [("a_serving", phase_serving), ("b_kernel_vs_ref", phase_routes),
               ("c_ultranet", phase_ultranet)])
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            line, problems = fn(ctx)
        except Exception as e:                  # report, keep going
            traceback.print_exc()
            line, problems = {}, [f"{type(e).__name__}: {e}"]
        line = dict(line, phase_s=time.perf_counter() - t0,
                    ok=not problems, problems=problems)
        print(f"phase {name} {json.dumps(line)}", flush=True)
        if problems:
            failed.append(name)
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
