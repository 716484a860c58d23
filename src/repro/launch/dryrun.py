import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

# ^ must precede every other import: jax locks the device count on first
# initialization.  512 host devices stand in for 2 pods x 256 chips.

import argparse        # noqa: E402
import json            # noqa: E402
import re              # noqa: E402
import sys             # noqa: E402
import time            # noqa: E402

import jax             # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import PartitionSpec  # noqa: E402

from repro.configs.base import SHAPES, ArchConfig, ShapeCell  # noqa: E402
from repro.configs.registry import ARCHS, get_arch  # noqa: E402
from repro.models import (init_cache, init_params, values, specs,  # noqa: E402
                          serve_params)
from repro.models.quantized import serve_param_specs  # noqa: E402
from repro.models import shard_ctx  # noqa: E402
from repro.models.param import P, is_p  # noqa: E402
from repro.train import loop, optimizer  # noqa: E402
from repro.launch.mesh import (HW, batch_shardings,  # noqa: E402
                               make_production_mesh, rules_for_mesh,
                               shardings_of)

COLLECTIVE_RE = re.compile(
    r"\b(all-reduce|all-gather|reduce-scatter|all-to-all|"
    r"collective-permute)(?:-start)?(?:\.\d+)?\s*=?\s*"
    r"\(?\s*((?:[a-z0-9]+\[[0-9,]*\][,\s]*)+)")
SHAPE_RE = re.compile(r"([a-z][a-z0-9]*)\[([0-9,]*)\]")

DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
               "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
               "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1}


def collective_bytes(hlo_text: str) -> dict:
    """Sum per-device collective operand bytes from optimized HLO."""
    out = {}
    for m in COLLECTIVE_RE.finditer(hlo_text):
        op = m.group(1)
        size = 0
        for dt, dims in SHAPE_RE.findall(m.group(2)):
            n = 1
            for d in dims.split(","):
                if d:
                    n *= int(d)
            size += n * DTYPE_BYTES.get(dt, 4)
        out[op] = out.get(op, 0) + size
    out["total"] = sum(v for k, v in out.items() if k != "total")
    return out


def abstract_batch(cfg: ArchConfig, b: int, s: int, *, kind: str):
    f32 = jnp.float32
    i32 = jnp.int32
    if kind == "decode":
        return {"tokens": jax.ShapeDtypeStruct((b, 1), i32)}
    if cfg.family == "encdec":
        s_src = s // 2
        return {"src": jax.ShapeDtypeStruct((b, s_src, cfg.d_model), f32),
                "tokens": jax.ShapeDtypeStruct((b, s - s_src), i32)}
    if cfg.family == "vlm":
        return {"tokens": jax.ShapeDtypeStruct((b, s - cfg.n_patches), i32),
                "patches": jax.ShapeDtypeStruct(
                    (b, cfg.n_patches, cfg.d_model), f32)}
    return {"tokens": jax.ShapeDtypeStruct((b, s), i32)}


def opt_spec_tree(ocfg: optimizer.OptConfig, params_p):
    def f(p: P):
        v, sp = p.value, p.spec
        size = 1
        for d in v.shape:
            size *= int(d)
        if ocfg.moments_8bit and v.ndim >= 1 and size >= 4096:
            full = list(sp) + [None] * (v.ndim - len(sp))
            return optimizer.Q8(q=PartitionSpec(*full),
                                scale=PartitionSpec(*full[:-1], None))
        return sp
    m = jax.tree_util.tree_map(f, params_p, is_leaf=is_p)
    return {"m": m, "v": m, "step": PartitionSpec()}


def build_cell(cfg: ArchConfig, shape: ShapeCell, mesh):
    """Returns (fn, args_abstract, in_shardings, donate) for one cell."""
    import dataclasses as _dc
    rules = rules_for_mesh(mesh, fsdp=cfg.fsdp)
    # batch=1 cells (long_500k) cannot shard the batch axis; degrade to
    # replicated batch (the O(1)-state archs this shape targets don't
    # need it).
    bsize = 1
    for ax in rules.batch:
        bsize *= mesh.shape[ax]
    if shape.global_batch % max(1, bsize):
        rules = _dc.replace(rules, batch=(), batch_degree=1)
    params_p = init_params(cfg, rules, None)
    pvals, pspecs = values(params_p), specs(params_p)
    b, s = shape.global_batch, shape.seq_len

    if shape.kind == "train":
        ocfg = optimizer.OptConfig(moments_8bit=cfg.opt_8bit,
                                   total_steps=10000)
        opt_abs = loop.abstract_opt_state(ocfg, pvals)
        opt_specs = opt_spec_tree(ocfg, params_p)
        batch = abstract_batch(cfg, b, s, kind="train")
        fn = loop.make_train_step(cfg, ocfg,
                                  microbatches=cfg.train_microbatches)
        in_sh = (shardings_of(mesh, pspecs), shardings_of(mesh, opt_specs),
                 batch_shardings(mesh, rules, batch))
        return rules, fn, (pvals, opt_abs, batch), in_sh, (0, 1)

    # serving paths run on quantized lane-packed weights (the paper's
    # packing applied to HBM layout)
    qvals = jax.eval_shape(
        lambda p: serve_params(p, bits=cfg.serve_weight_bits), pvals)
    qspecs = serve_param_specs(pvals, pspecs, cfg.serve_weight_bits)

    if shape.kind == "prefill":
        from repro.models import forward
        batch = abstract_batch(cfg, b, s, kind="prefill")
        fn = lambda p, bt: forward(cfg, p, bt, diff=False,  # noqa: E731
                                   mode="last_logits")
        in_sh = (shardings_of(mesh, qspecs),
                 batch_shardings(mesh, rules, batch))
        return rules, fn, (qvals, batch), in_sh, ()

    if shape.kind == "decode":
        from repro.models import decode_step
        cache_p = init_cache(cfg, rules, b, s, abstract=True)
        cvals, cspecs = values(cache_p), specs(cache_p)
        batch = abstract_batch(cfg, b, s, kind="decode")
        fn = lambda p, c, t: decode_step(cfg, p, c, t["tokens"])  # noqa: E731
        in_sh = (shardings_of(mesh, qspecs), shardings_of(mesh, cspecs),
                 batch_shardings(mesh, rules, batch))
        return rules, fn, (qvals, cvals, batch), in_sh, (1,)

    raise ValueError(shape.kind)


def run_cell(arch: str, shape_name: str, *, multi_pod: bool,
             verbose: bool = True) -> dict:
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    okay, why = cfg.shape_supported(shape)
    if not okay:
        return {"arch": cfg.name, "shape": shape_name,
                "mesh": "multi" if multi_pod else "single",
                "status": "skipped", "reason": why}
    mesh = make_production_mesh(multi_pod=multi_pod)
    rules, fn, args, in_sh, donate = build_cell(cfg, shape, mesh)
    t0 = time.time()
    with mesh:
        with shard_ctx.use_rules(rules):
            lowered = jax.jit(fn, in_shardings=in_sh,
                              donate_argnums=donate).lower(*args)
            compiled = lowered.compile()
    t1 = time.time()
    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo = compiled.as_text()
    coll = collective_bytes(hlo)
    n_dev = mesh.size
    res = {
        "arch": cfg.name, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "status": "ok",
        "compile_s": round(t1 - t0, 1),
        "devices": n_dev,
        "flops_per_device": cost.get("flops", -1.0),
        "bytes_per_device": cost.get("bytes accessed", -1.0),
        "collective_bytes_per_device": coll.get("total", 0),
        "collectives": {k: v for k, v in coll.items() if k != "total"},
        "argument_bytes": getattr(mem, "argument_size_in_bytes", 0),
        "output_bytes": getattr(mem, "output_size_in_bytes", 0),
        "temp_bytes": getattr(mem, "temp_size_in_bytes", 0),
        "peak_bytes": getattr(mem, "peak_memory_in_bytes", 0) or
        (getattr(mem, "argument_size_in_bytes", 0)
         + getattr(mem, "temp_size_in_bytes", 0)),
    }
    if verbose:
        print(f"[{res['arch']} x {shape_name} x {res['mesh']}] "
              f"compile {res['compile_s']}s  "
              f"flops/dev {res['flops_per_device']:.3e}  "
              f"bytes/dev {res['bytes_per_device']:.3e}  "
              f"coll/dev {res['collective_bytes_per_device']:.3e}  "
              f"arg+temp {(res['argument_bytes'] + res['temp_bytes'])/2**30:.2f} GiB")
    return res


def main():
    ap = argparse.ArgumentParser(description="multi-pod dry-run")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", choices=["single", "multi", "both"],
                    default="both")
    ap.add_argument("--out", default=None, help="append JSONL here")
    args = ap.parse_args()

    archs = list(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"single": [False], "multi": [True],
              "both": [False, True]}[args.mesh]

    n_fail = 0
    results = []
    for a in archs:
        for sh in shapes:
            for mp in meshes:
                try:
                    res = run_cell(a, sh, multi_pod=mp)
                except Exception as e:  # noqa: BLE001
                    res = {"arch": a, "shape": sh,
                           "mesh": "2x16x16" if mp else "16x16",
                           "status": "fail", "error": f"{type(e).__name__}: {e}"}
                    print(f"[{a} x {sh} x {res['mesh']}] FAIL: "
                          f"{res['error']}", file=sys.stderr)
                    n_fail += 1
                results.append(res)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(json.dumps(res) + "\n")
    okc = sum(1 for r in results if r["status"] == "ok")
    skc = sum(1 for r in results if r["status"] == "skipped")
    print(f"\ndry-run: {okc} ok, {skc} skipped, {n_fail} failed "
          f"of {len(results)} cells")
    sys.exit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
