"""Production training launcher.

On real hardware this is the entrypoint per host; here it runs on the
local device set (optionally multi-device via
XLA_FLAGS=--xla_force_host_platform_device_count=N) with the full
substrate: mesh + sharding rules, deterministic host-sharded data,
AdamW (+8-bit moments), microbatching, async checkpointing with resume,
straggler monitoring, SIGTERM emergency save.  The step loop itself is
``train/loop.run_training`` — device sync inside the timed region.

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --smoke --steps 50 --mesh 2,2

``--qat`` switches to the packed QAT driver (``train/qat``): STE
forward through the packed datapath, export to serving-ready params.

  PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
      --smoke --steps 20 --qat --w-bits 4 --a-bits 8 \
      --plan-cache /tmp/qat_plans.json --export /tmp/qat_serve.ck
"""
from __future__ import annotations

import argparse

import jax


def run_qat_main(args) -> None:
    """--qat path: single-host packed QAT via ``train/qat/loop``."""
    from repro.train import qat

    qcfg = qat.QATRunConfig(
        arch=args.arch, smoke=args.smoke, steps=args.steps,
        global_batch=args.global_batch, seq=args.seq,
        microbatches=args.microbatches,
        w_bits=args.w_bits, a_bits=args.a_bits,
        min_size=args.qat_min_size,
        packed_forward=not args.float_forward,
        plan_policy="cache" if args.plan_cache else "auto",
        plan_cache=args.plan_cache or None,
        ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
        resume=args.resume)

    precision = None
    if args.bitsearch:
        from repro.train.loop import init_run
        cfg, _, params, _, _ = init_run(args.arch, smoke=args.smoke)
        precision, report = qat.search_bitwidths(
            params, min_size=args.qat_min_size,
            cache_path=args.plan_cache or None)
        qat.write_search_report(report, args.bitsearch,
                                {"arch": cfg.name})
        print(f"bitsearch: {len(report)} layers -> {args.bitsearch}")

    res = qat.run_qat(qcfg, precision=precision)
    print(f"qat: {res['qat_layers']} packed layers, "
          f"eval {res['qat_eval']:.4f} "
          f"(float init {res['float_eval_at_init']:.4f})")
    if args.export:
        from repro.train import checkpoint
        served = qat.export_for_serving(qcfg, res["params"])
        checkpoint.save(args.export, qcfg.steps, served)
        print(f"exported serving params -> {args.export}")


def setup_training(cfg, mesh, *, steps: int, seq: int, global_batch: int,
                   seed: int = 0):
    """The float launcher's state on ``mesh``: parameters placed by the
    sharding rules (FSDP over ``data`` when ``cfg.fsdp``), AdamW state
    built from them, the deterministic synthetic data stream and the
    function that shards each host batch along the batch axis.

    Returns ``(rules, ocfg, params, opt, data, place_batch)``."""
    from repro.data import SyntheticLMData
    from repro.models import init_params, specs, values
    from repro.train import optimizer
    from repro.launch.mesh import (batch_shardings, rules_for_mesh,
                                   shardings_of)

    rules = rules_for_mesh(mesh, fsdp=cfg.fsdp)
    pt = init_params(cfg, rules, jax.random.PRNGKey(seed))
    pv, ps = values(pt), specs(pt)
    pv = jax.device_put(pv, shardings_of(mesh, ps))
    ocfg = optimizer.OptConfig(lr=3e-4, warmup=10, total_steps=steps,
                               moments_8bit=cfg.opt_8bit)
    opt = optimizer.init(ocfg, pv)
    data = SyntheticLMData(
        vocab=cfg.vocab, seq_len=seq, global_batch=global_batch,
        seed=seed, n_patches=cfg.n_patches, d_model=cfg.d_model,
        encdec=cfg.family == "encdec")

    def place_batch(host):
        shards = batch_shardings(mesh, rules, host)
        return {k: jax.device_put(v, shards[k]) for k, v in host.items()}

    return rules, ocfg, pv, opt, data, place_batch


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b")
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--microbatches", type=int, default=2)
    ap.add_argument("--mesh", default="",
                    help="data,model (default: all devices on data)")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_launch_train")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    # --- QAT mode ---
    ap.add_argument("--qat", action="store_true",
                    help="packed quantization-aware training")
    ap.add_argument("--w-bits", type=int, default=4)
    ap.add_argument("--a-bits", type=int, default=8)
    ap.add_argument("--qat-min-size", type=int, default=1 << 10,
                    help="smallest kernel (elements) to fake-quantize")
    ap.add_argument("--float-forward", action="store_true",
                    help="QAT with the unpacked integer-decode forward")
    ap.add_argument("--plan-cache", default="",
                    help="plan-cache JSON path (warmed by --bitsearch)")
    ap.add_argument("--bitsearch", default="",
                    help="run bitwidth search first; write report here")
    ap.add_argument("--export", default="",
                    help="checkpoint dir for serving-ready params")
    args = ap.parse_args()

    if args.qat:
        run_qat_main(args)
        return

    from repro.configs.registry import get_arch
    from repro.models import shard_ctx
    from repro.train import checkpoint, loop
    from repro.launch.mesh import make_mesh

    cfg = get_arch(args.arch)
    if args.smoke:
        cfg = cfg.reduced()
    nd = jax.device_count()
    if args.mesh:
        dd, mm = (int(x) for x in args.mesh.split(","))
    else:
        dd, mm = nd, 1
    mesh = make_mesh((dd, mm), ("data", "model"))
    print(f"mesh {dict(mesh.shape)}  arch {cfg.name}")
    rules, ocfg, pv, opt, data, place_batch = setup_training(
        cfg, mesh, steps=args.steps, seq=args.seq,
        global_batch=args.global_batch)

    start = 0
    if args.resume:
        last = checkpoint.latest_step(args.ckpt_dir)
        if last is not None:
            (pv, opt), meta = checkpoint.restore(args.ckpt_dir, last,
                                                 (pv, opt))
            start = meta["step"]
            print(f"resumed at step {start}")

    ck = checkpoint.AsyncCheckpointer(args.ckpt_dir)
    state = {"pv": pv, "opt": opt, "step": start}
    checkpoint.install_sigterm_handler(
        lambda: (ck.wait(), checkpoint.save(
            args.ckpt_dir, state["step"], (state["pv"], state["opt"]))))

    def on_step(s, p, o, m, dt, mon):
        state.update(pv=p, opt=o, step=s + 1)
        if mon.should_mitigate:
            print("[straggler] mitigation trigger")
        if (s + 1) % args.ckpt_every == 0 or s + 1 == args.steps:
            ck.save_async(s + 1, (p, o))
        if (s + 1) % 10 == 0 or s == start:
            print(f"step {s+1:4d} loss {float(m['loss']):.4f} "
                  f"lr {float(m['lr']):.2e}")

    with mesh:
        with shard_ctx.use_rules(rules):
            pv, opt, _, _ = loop.run_training(
                cfg, ocfg, pv, opt, data, steps=args.steps, start=start,
                microbatches=args.microbatches, place_batch=place_batch,
                on_step=on_step)
    ck.wait()


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
