"""Roofline analysis per (arch x shape x mesh) — deliverable (g).

Three terms, in seconds per step, per chip (TPU v5e model):

    compute    = HLO_FLOPs / (chips * 197e12)
    memory     = HLO_bytes / (chips * 819e9)
    collective = collective_bytes / (chips * 50e9)

Sources and the loop-count correction
-------------------------------------
``compiled.cost_analysis()`` counts a while-loop body exactly ONCE, so a
scan-over-layers program under-reports FLOPs/bytes by ~L x.  We correct
with two auxiliary *unrolled* lowerings at full width: f(1 layer) and
f(2 layers) with every inner scan disabled (single-chunk attention,
single-chunk CE loss, no microbatching) give

    total(L) = f(1) + (L - 1) * [f(2) - f(1)]

which is loop-free HLO arithmetic, not an analytical guess.  The same
delta corrects per-layer collective bytes (FSDP all-gathers, TP
reduces); step-level collectives (gradient all-reduce) live in f(1)'s
base.  Families with non-layer inner loops (SSD chunk scan) additionally
multiply the known trip count into the block term — noted per row.

MODEL_FLOPS = 6*N*D (dense) or 6*N_active*D (MoE); the ratio
MODEL_FLOPS / HLO_FLOPs measures how much compiled compute is "useful"
(catches remat/correction/attention overhead).
"""
import os
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=512")

import argparse          # noqa: E402
import dataclasses       # noqa: E402
import json              # noqa: E402
import sys               # noqa: E402

import jax               # noqa: E402

from repro.configs.base import SHAPES, param_count, active_param_count  # noqa: E402
from repro.configs.registry import ARCHS, get_arch  # noqa: E402
from repro.launch.mesh import HW, make_production_mesh  # noqa: E402
from repro.launch import dryrun as DR  # noqa: E402
from repro.models import shard_ctx  # noqa: E402

PEAK = HW["peak_flops_bf16"]
HBM = HW["hbm_bw"]
ICI = HW["ici_bw"]


def _family_layer_counts(cfg):
    """(small_cfgs, multiplier) for the delta-layer correction."""
    if cfg.family == "moe" and cfg.moe_every > 1:
        me = cfg.moe_every
        return [me, 2 * me], cfg.n_layers // me
    if cfg.family == "hybrid":
        # groups of 3; tail approximated as 2/3 group (2 rec layers)
        return [3, 6], (cfg.n_layers // 3) + (2 / 3) \
            * (cfg.n_layers - 3 * (cfg.n_layers // 3)) / 1.0
    if cfg.family == "encdec":
        return [1, 2], cfg.n_enc_layers  # enc+dec pairs scale together
    return [1, 2], cfg.n_layers


def _small_cfg(cfg, n, shape):
    kw = dict(scan_layers=False, train_microbatches=1,
              attn_chunk=shape.seq_len, fsdp=cfg.fsdp)
    if cfg.family == "encdec":
        return dataclasses.replace(cfg, n_layers=2 * n, n_enc_layers=n,
                                   n_dec_layers=n, **kw)
    return dataclasses.replace(cfg, n_layers=n, **kw)


def _lower_cost(cfg, shape, mesh):
    rules, fn, args, in_sh, donate = DR.build_cell(cfg, shape, mesh)
    with mesh:
        with shard_ctx.use_rules(rules):
            compiled = jax.jit(fn, in_shardings=in_sh,
                               donate_argnums=donate).lower(*args).compile()
    cost = compiled.cost_analysis()
    coll = DR.collective_bytes(compiled.as_text())
    return {"flops": cost.get("flops", 0.0),
            "bytes": cost.get("bytes accessed", 0.0),
            "coll": float(coll.get("total", 0))}


def corrected_cell(arch: str, shape_name: str):
    """Delta-layer-corrected per-device HLO flops/bytes/collectives."""
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    ok, why = cfg.shape_supported(shape)
    if not ok:
        return {"status": "skipped", "reason": why}
    mesh = make_production_mesh(multi_pod=False)
    ns, mult = _family_layer_counts(cfg)
    f1 = _lower_cost(_small_cfg(cfg, ns[0], shape), shape, mesh)
    f2 = _lower_cost(_small_cfg(cfg, ns[1], shape), shape, mesh)
    out = {"status": "ok"}
    # SSD / loss / conv inner scans are loop-free in these cfgs except
    # the mamba chunk scan, which both f1 and f2 contain once per layer
    # (noted: its per-chunk body is multiplied below).
    ssd_trips = 1
    if cfg.family == "ssm" and shape.kind != "decode":
        ssd_trips = max(1, shape.seq_len // 256)
    for k in ("flops", "bytes", "coll"):
        d = f2[k] - f1[k]
        base = f1[k] - d  # non-layer part
        per_layer = d * (ssd_trips if k == "flops" and ssd_trips > 1 else 1)
        out[k] = max(0.0, base) + mult * per_layer
    out["raw_f1"] = f1
    out["raw_f2"] = f2
    return out


def terms(flops, bytes_, coll, chips=256):
    t_c = flops / PEAK
    t_m = bytes_ / HBM
    t_x = coll / ICI
    dom = max((t_c, "compute"), (t_m, "memory"), (t_x, "collective"))
    return {"t_compute_s": t_c, "t_memory_s": t_m, "t_collective_s": t_x,
            "bottleneck": dom[1],
            "roofline_frac": dom[0] and max(t_c, t_m, t_x) and
            (t_c / max(t_c, t_m, t_x))}


SUGGEST = {
    ("memory", "decode"): "quantize/pack the KV cache (int4 lanes) and "
                          "batch more requests per weight read",
    ("memory", "train"): "raise arithmetic intensity: larger microbatch "
                         "per device, fuse optimizer, bf16 grads",
    ("memory", "prefill"): "tighter attention tiling / fused unpack-matmul",
    ("collective", "train"): "int8 gradient all-reduce (grad_compress), "
                             "overlap FSDP gathers with compute",
    ("collective", "decode"): "resharding: keep KV and heads co-located "
                              "to kill per-layer all-reduces",
    ("collective", "prefill"): "sequence-parallel norms to shrink "
                               "activation gathers",
    ("compute", "train"): "already compute-bound: raise MFU via larger "
                          "matmul tiles / less remat",
    ("compute", "prefill"): "compute-bound: good; check causal-flops "
                            "waste in attention tiling",
    ("compute", "decode"): "compute-bound decode is unusual: check "
                           "correction-logic overhead from packing",
}


def analytic_bytes(cfg, shape, chips=256):
    """Per-step global HBM traffic model (documented napkin math):

    train:   params 2x bf16 read (fwd+bwd) + grad f32 r/w + opt m,v r/w
             (f32, or int8+scales when opt_8bit) + param write
             + activation layer-boundary traffic (save+read, bf16)
             + attention KV block traffic (~3 passes fwd+bwd)
    prefill: params once (w4 packed) + activations + KV cache write
    decode:  packed weights once + KV cache read (+write of 1 slot)
    """
    n = param_count(cfg)
    n_act = active_param_count(cfg)
    b, sl = shape.global_batch, shape.seq_len
    d, L = cfg.d_model, max(1, cfg.n_layers)
    kvh = (cfg.n_kv or 0) * cfg.hd
    if shape.kind == "train":
        opt_bytes = (2 if cfg.opt_8bit else 8) * 2 * n
        acts = 4 * L * b * sl * d * 2
        attn = 3 * L * b * sl * kvh * 2 * 2
        return 2 * n * 2 + 2 * n * 4 + opt_bytes + n * 2 + acts + attn
    wbits = cfg.serve_weight_bits
    if shape.kind == "prefill":
        acts = 2 * L * b * sl * d * 2
        kv_write = L * b * sl * kvh * 2 * 2
        return n * wbits / 8 + acts + kv_write
    # decode: one token against the cache
    kv_bytes = 1 if cfg.serve_kv_bits == 8 else 2
    cache = L * b * sl * kvh * 2 * kv_bytes
    if cfg.family == "ssm":
        cache = L * b * (cfg.ssm_heads * cfg.ssm_state * cfg.hd0
                         if False else cfg.d_inner // max(1, cfg.ssm_heads)
                         * cfg.ssm_heads * cfg.ssm_state) * 4
    if cfg.family == "hybrid":
        w = min(cfg.window or sl, sl)
        cache = (cfg.n_layers // 3) * b * w * kvh * 2 * kv_bytes \
            + cfg.n_layers * b * cfg.d_rnn * 4
    return n_act * wbits / 8 + cache


def model_flops(cfg, shape):
    n_act = active_param_count(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n_act * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n_act * tokens
    return 2.0 * n_act * shape.global_batch     # one token per request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--dryrun-jsonl", default="results/dryrun.jsonl")
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--out", default="results/roofline.jsonl")
    ap.add_argument("--no-correct", action="store_true",
                    help="report raw dry-run numbers only")
    args = ap.parse_args()

    archs = list(ARCHS) if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    raw = {}
    if os.path.exists(args.dryrun_jsonl):
        for line in open(args.dryrun_jsonl):
            r = json.loads(line)
            raw[(r["arch"], r["shape"], r["mesh"])] = r

    rows = []
    for a in archs:
        cfg = get_arch(a)
        for sh in shapes:
            shape = SHAPES[sh]
            ok, why = cfg.shape_supported(shape)
            if not ok:
                rows.append({"arch": cfg.name, "shape": sh,
                             "status": "skipped", "reason": why})
                continue
            try:
                cor = {"status": "raw"} if args.no_correct \
                    else corrected_cell(a, sh)
            except Exception as e:   # noqa: BLE001
                cor = {"status": "fail", "error": str(e)}
            base = raw.get((cfg.name, sh, "16x16"), {})
            if cor.get("status") == "ok":
                # corrected_cell numbers are PER-DEVICE (SPMD module)
                fl = cor["flops"] * 256
                by = cor["bytes"] * 256
                co = cor["coll"] * 256
            else:
                fl = base.get("flops_per_device", 0) * 256
                by = base.get("bytes_per_device", 0) * 256
                co = base.get("collective_bytes_per_device", 0) * 256
            ab = analytic_bytes(cfg, shape)
            # memory term uses the analytic traffic model: HLO "bytes
            # accessed" on the CPU backend counts unfused operand
            # traffic (pessimistic by >10x); both are reported.
            t = terms(fl / 256, ab / 256, co / 256)
            mf = model_flops(cfg, shape)
            row = {"arch": cfg.name, "shape": sh, "mesh": "16x16",
                   "status": cor.get("status"),
                   "hlo_flops_total": fl, "hlo_bytes_total": by,
                   "analytic_bytes_total": ab,
                   "collective_bytes_total": co,
                   **t,
                   "model_flops_6nd": mf,
                   "useful_ratio": mf / fl if fl else 0.0,
                   "suggestion": SUGGEST.get((t["bottleneck"], shape.kind),
                                             ""),
                   "peak_bytes_per_dev": base.get("peak_bytes", 0),
                   "raw_dryrun": {k: base.get(k) for k in
                                  ("flops_per_device", "bytes_per_device",
                                   "collective_bytes_per_device")}}
            rows.append(row)
            print(f"{cfg.name:26s} {sh:12s} "
                  f"C {row.get('t_compute_s', 0):.3e}s "
                  f"M {row.get('t_memory_s', 0):.3e}s "
                  f"X {row.get('t_collective_s', 0):.3e}s "
                  f"-> {row.get('bottleneck', '-'):10s} "
                  f"useful {row.get('useful_ratio', 0):.2f}")
    os.makedirs(os.path.dirname(args.out), exist_ok=True)
    with open(args.out, "w") as f:
        for r in rows:
            f.write(json.dumps(r) + "\n")
    print(f"wrote {args.out} ({len(rows)} rows)")


if __name__ == "__main__":
    main()
