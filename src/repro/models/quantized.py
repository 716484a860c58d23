"""Quantized, lane-packed serving parameters.

``serve_params`` rewrites a trained parameter tree; the layer library
transparently dispatches on the container type, so ``decode_step``/
``forward`` run unchanged.  Two packing modes:

  * ``compute="memory"`` (``packed_memory``): every large projection
    kernel becomes a ``PackedLinear`` — w-bit symmetric per-output-
    channel quantization, 32/w values per int32 lane word in HBM; the
    paper's packing applied to the TPU memory roofline.
  * ``compute="sdv"`` (``packed_compute_sdv``): projection kernels —
    2-D leaves and scanned layer stacks of them — become ``SDVLinear``:
    the same quantization stored as SDV words ([K, G], n output
    channels lane-packed per word), executed through the
    ``kernels/ops.packed_matmul`` dispatch layer so batched
    decode/prefill GEMMs run on the packed arithmetic datapath
    (activations are dynamically quantized per row to ``plan.w_b``
    bits).  Unstacked >2-D kernels (MoE expert banks) keep the
    memory packing.  The short depthwise conv of the SSM/Griffin blocks
    becomes ``BSEGConv`` — taps BSEG-packed through the pre-adder,
    executed via the ``kernels/ops`` packed-conv dispatch (activations
    dynamically quantized to the unsigned ``plan.w_i``-bit domain with
    a zero point, per Eqs. 9/10).

See DESIGN.md §2 for when each mode wins.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.core.datapath import BSEGPlan, INT32, SDVPlan, plan_bseg, plan_sdv
from repro.quant import quantizer


@dataclasses.dataclass
class PackedLinear:
    """Lane-packed quantized kernel: words [..., d_in, d_out/per] int32,
    scale [..., 1, d_out_pad] f32; ``d_out`` unpads on materialize."""
    words: jnp.ndarray
    scale: jnp.ndarray
    bits: int
    d_out: int


jax.tree_util.register_dataclass(PackedLinear, data_fields=["words", "scale"],
                                 meta_fields=["bits", "d_out"])


@dataclasses.dataclass
class SDVLinear:
    """Arithmetic-packed quantized kernel: SDV storage words
    [d_in, G] int32 (G = ceil(d_out/plan.n) lane groups) — or
    [2, d_in, G] limb planes for the wide (2-limb) DSP48E2/DSP58
    plans — scale [d_out] f32; executed via
    ``kernels/ops.packed_matmul``.  A scanned layer stack keeps a
    leading layer axis on ``words``/``scale`` ([L, d_in, G] /
    [L, 2, d_in, G] / [L, d_out]); ``lax.scan`` slices it back off,
    yielding the per-layer container unchanged (same pattern as
    ``BSEGConv``).

    ``use_kernel`` pins the route of ``sdv_matmul_apply``: ``None``
    follows the backend (Pallas on TPU, the jnp ref decode on CPU);
    ``True``/``False`` pin the kernel/ref route on any backend — the
    on-chip kernel-vs-ref comparison sets ``False`` on a copy of the
    tree, which shares every array."""
    words: jnp.ndarray
    scale: jnp.ndarray
    plan: SDVPlan
    d_out: int
    use_kernel: Optional[bool] = None


jax.tree_util.register_dataclass(SDVLinear, data_fields=["words", "scale"],
                                 meta_fields=["plan", "d_out", "use_kernel"])


def pack_linear(kernel: jnp.ndarray, bits: int) -> PackedLinear:
    """kernel [..., d_in, d_out] float -> PackedLinear."""
    per = 32 // bits
    amax = jnp.max(jnp.abs(kernel.astype(jnp.float32)), axis=-2,
                   keepdims=True)
    scale = quantizer.symmetric_scale(amax, bits)
    q = quantizer.symmetric_qvalues(kernel.astype(jnp.float32), scale,
                                    bits).astype(jnp.int32)
    d_out = kernel.shape[-1]
    pad = (-d_out) % per
    if pad:
        q = jnp.pad(q, [(0, 0)] * (q.ndim - 1) + [(0, pad)])
        scale = jnp.pad(scale, [(0, 0)] * (scale.ndim - 1) + [(0, pad)],
                        constant_values=1.0)
    nw = (d_out + pad) // per
    words = jnp.zeros(q.shape[:-1] + (nw,), jnp.int32)
    for i in range(per):
        field = q[..., i::per] & ((1 << bits) - 1)
        words = words | (field << (i * bits))
    return PackedLinear(words=words, scale=scale.astype(jnp.float32),
                        bits=bits, d_out=d_out)


@dataclasses.dataclass(frozen=True)
class LayerStream:
    """A parameter tree whose scanned layers are made one at a time.

    ``shared`` holds every top-level entry but ``blocks``;
    ``make(name, i)`` makes entry ``name`` of ``blocks`` for scan step
    ``i`` (its leaves without the leading scan axis); ``names`` lists
    the entries and ``depth`` the scan steps.  ``serve_params`` packs a
    stream one layer at a time, so the float weights of one layer at
    most exist at once, and gives bitwise the tree it gives for the
    whole stacked tree."""
    shared: Dict[str, Any]
    make: Callable[[str, int], Any]
    names: Tuple[str, ...]
    depth: int


@functools.partial(jax.jit, donate_argnums=0)
def _put_leaves(stack, i, layer):
    return jax.tree_util.tree_map(lambda a, b: a.at[i].set(b), stack, layer)


def _serve_stream(stream: LayerStream, bits: int, min_size: int,
                  compute: str) -> Dict[str, Any]:
    """``serve_params`` of a ``LayerStream``: the shared entries packed
    as a whole, then each layer made, packed and written in place into
    its stacked result (memory packing only: per output channel, so a
    layer packs alone exactly as it packs in its stack)."""
    if compute != "memory":
        raise ValueError("a LayerStream packs on the memory route only")
    out = serve_params(stream.shared, bits=bits, min_size=min_size)
    # a stacked leaf packs where depth x its size reaches min_size
    layer_min = -(-min_size // stream.depth)
    blocks = {}
    for name in stream.names:
        stack = None
        for i in range(stream.depth):
            layer = serve_params(stream.make(name, i), bits=bits,
                                 min_size=layer_min)
            if stack is None:
                stack = jax.tree_util.tree_map(
                    lambda a: jnp.zeros((stream.depth,) + a.shape, a.dtype),
                    layer)
            stack = _put_leaves(stack, i, layer)
            del layer
        blocks[name] = stack
    out["blocks"] = blocks
    return out


def default_sdv_plan(bits: int, act_bits: int = 8) -> SDVPlan:
    """The serving lane plan: ``bits``-wide signed weights against
    ``act_bits``-wide signed activations on the TPU int32 datapath."""
    return plan_sdv(INT32, bits, act_bits, signed_a=True, signed_b=True,
                    park_sign_bits=True)


def pack_linear_sdv(kernel: jnp.ndarray, plan: SDVPlan) -> SDVLinear:
    """kernel [d_in, d_out] float -> SDVLinear (w_a-bit symmetric
    per-output-channel quantization stored as SDV words).  A stacked
    [L, d_in, d_out] kernel (scanned blocks) packs each layer with the
    shared plan and keeps the layer axis on every data field."""
    assert kernel.ndim in (2, 3), kernel.shape
    if kernel.ndim == 3:
        # one layer at a time, written in place into the stacked
        # result: neither the temporaries of a whole [L, d_in, d_out]
        # stack nor a list-then-stack copy of its words ever exists
        words = scale = None
        for i in range(kernel.shape[0]):
            layer = pack_linear_sdv(kernel[i], plan)
            if words is None:
                words = jnp.zeros((kernel.shape[0],) + layer.words.shape,
                                  layer.words.dtype)
                scale = jnp.zeros((kernel.shape[0],) + layer.scale.shape,
                                  layer.scale.dtype)
            words, scale = _put_layer(words, scale, i, layer.words,
                                      layer.scale)
        return SDVLinear(words=words, scale=scale, plan=plan,
                         d_out=kernel.shape[-1])
    kf = kernel.astype(jnp.float32)
    scale = quantizer.symmetric_scale(jnp.max(jnp.abs(kf), axis=0),
                                      plan.w_a)
    q = quantizer.symmetric_qvalues(kf, scale, plan.w_a).astype(jnp.int32)
    del kf
    # the word packing is integer-exact, so it runs as one compiled
    # program per shape instead of a stream of eager limb ops
    words = _pack_sdv_words(q, plan)                         # [d_in, G]
    return SDVLinear(words=words, scale=scale.astype(jnp.float32),
                     plan=plan, d_out=kernel.shape[-1])


@functools.partial(jax.jit, static_argnums=1)
def _pack_sdv_words(q: jnp.ndarray, plan: SDVPlan) -> jnp.ndarray:
    from repro.kernels import ops
    return ops.prepare_sdv_weights(q.T, plan)


@functools.partial(jax.jit, donate_argnums=(0, 1))
def _put_layer(words, scale, i, layer_words, layer_scale):
    return words.at[i].set(layer_words), scale.at[i].set(layer_scale)


def sdv_matmul_apply(qw: SDVLinear, x: jnp.ndarray) -> jnp.ndarray:
    """x [..., d_in] @ SDV-packed kernel -> [..., d_out] in x.dtype.

    Activations are dynamically quantized per row (symmetric,
    ``plan.w_b`` bits); the integer GEMM goes through the
    ``packed_matmul`` dispatch layer, the two scales dequantize the
    exact int32 lane results.  The route follows the container's
    ``use_kernel``; unpinned, the backend: Pallas on TPU, the pure-jnp
    SDV-word decode path on CPU (interpret mode is for tests, not
    serving).
    """
    from repro.kernels import ops
    use_kernel = qw.use_kernel
    if use_kernel is None:
        use_kernel = jax.default_backend() != "cpu"
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
    xs = quantizer.symmetric_scale(amax, qw.plan.w_b)
    xq = quantizer.symmetric_qvalues(xf, xs, qw.plan.w_b).astype(jnp.int32)
    y = ops.packed_matmul(xq, qw.words, plan=qw.plan, m=qw.d_out,
                          use_kernel=use_kernel)
    return (y.astype(jnp.float32) * xs * qw.scale[None, :]).astype(x.dtype)


@dataclasses.dataclass
class BSEGConv:
    """Arithmetic-packed short depthwise conv: ``kappa`` [G, C] int32
    packed tap-group factors (pre-adder applied; [2, G, C] limb planes
    on the wide 2-limb plans), ``tap_sum`` [C] i32
    for the zero-point correction, per-channel weight ``scale`` [C]
    f32, float ``bias`` [C]; executed via ``kernels/ops.bseg_conv1d``.
    """
    kappa: jnp.ndarray
    tap_sum: jnp.ndarray
    scale: jnp.ndarray
    bias: jnp.ndarray
    plan: BSEGPlan
    taps: int


jax.tree_util.register_dataclass(
    BSEGConv, data_fields=["kappa", "tap_sum", "scale", "bias"],
    meta_fields=["plan", "taps"])


def default_bseg_plan(bits: int, act_bits: int = 4) -> BSEGPlan:
    """The serving conv plan: ``bits``-wide signed taps against
    ``act_bits``-wide unsigned inputs on the TPU int32 datapath."""
    return plan_bseg(INT32, bits, act_bits)


def pack_conv_bseg(conv_params: dict, plan: BSEGPlan) -> BSEGConv:
    """{'w': [..., C, taps] float, 'b': [..., C]} -> BSEGConv (w_k-bit
    symmetric per-channel tap quantization, BSEG-packed through the
    pre-adder).  A leading layer-stack dim (scanned blocks) is kept on
    every data field, so per-layer slicing under ``lax.scan`` yields
    the per-layer container unchanged."""
    from repro.kernels import ops
    w, b = conv_params["w"], conv_params["b"]
    assert w.ndim in (2, 3), w.shape
    taps = w.shape[-1]
    wf = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(wf), axis=-1, keepdims=True)
    scale = quantizer.symmetric_scale(amax, plan.w_k)
    q = quantizer.symmetric_qvalues(wf, scale, plan.w_k).astype(jnp.int32)
    kappa, tap_sum = ops.prepare_bseg_taps(q.reshape(-1, taps), plan)
    if w.ndim == 3:                      # [L, C, taps] stacked blocks
        from repro.kernels import bseg_common
        stack, c = w.shape[0], w.shape[1]
        if bseg_common.word_spec(plan).limbs == 2:   # [2, G, L*C]
            kappa = kappa.reshape(2, -1, stack, c) \
                .transpose(2, 0, 1, 3)               # [L, 2, G, C]
        else:
            kappa = kappa.reshape(-1, stack, c).swapaxes(0, 1)  # [L, G, C]
        tap_sum = tap_sum.reshape(stack, c)
    return BSEGConv(kappa=kappa, tap_sum=tap_sum,
                    scale=scale[..., 0].astype(jnp.float32),
                    bias=b.astype(jnp.float32), plan=plan,
                    taps=taps)


def bseg_conv_apply(qc: BSEGConv, x: jnp.ndarray, *,
                    state: Optional[jnp.ndarray] = None,
                    use_kernel: Optional[bool] = None):
    """x [B, S, C] float through the BSEG-packed causal depthwise conv.

    Activations (history included) are dynamically quantized per call —
    asymmetric, to the *unsigned* ``plan.w_i``-bit datapath domain with
    zero point 2^(w_i - 1) — then the exact integer correlation runs
    through the ``kernels/ops.bseg_conv1d`` dispatch; the two scales
    and the tap sums dequantize.  Mirrors ``ssm.short_conv_apply``:
    returns (y [B, S, C], new_state [B, taps-1, C]).
    """
    from repro.kernels import ops
    if use_kernel is None:
        use_kernel = jax.default_backend() != "cpu"
    taps = qc.taps
    if state is None:
        state = jnp.zeros((x.shape[0], taps - 1, x.shape[2]), x.dtype)
    xfull = jnp.concatenate([state.astype(x.dtype), x], axis=1)
    xf = xfull.astype(jnp.float32)
    lo = jnp.min(xf)
    hi = jnp.max(xf)
    xs = quantizer.asymmetric_scale(lo, hi, qc.plan.w_i)
    zp = quantizer.asymmetric_zero_point(qc.plan.w_i)
    xq_u = quantizer.asymmetric_qvalues(xf, lo, xs, qc.plan.w_i)
    xq = (xq_u - zp).astype(jnp.int8)            # signed datapath input
    y_int = ops.bseg_conv1d(xq, qc.kappa, qc.tap_sum, plan=qc.plan,
                            n_taps=taps, zero_point=zp, padding="causal",
                            use_kernel=use_kernel)[:, taps - 1:, :]
    # sum_q w x = scale_w * xs * sum_q q*xq_u + lo * scale_w * sum_q q
    ts = qc.tap_sum.astype(jnp.float32)
    y = qc.scale * xs * (y_int.astype(jnp.float32) + zp * ts) \
        + lo * qc.scale * ts + qc.bias
    new_state = xfull[:, xfull.shape[1] - (taps - 1):, :]
    return y.astype(x.dtype), new_state


def materialize(pl, dtype=jnp.bfloat16) -> jnp.ndarray:
    """Unpack + dequantize -> [..., d_in, d_out] in ``dtype``."""
    if isinstance(pl, SDVLinear):
        from repro.kernels import bseg_common, ref
        # per-layer words are [K, G], or [2, K, G] limb planes on the
        # wide (2-limb) plans — one extra axis on top means a stack
        base = 2 + (bseg_common.sdv_word_spec(pl.plan).limbs == 2)
        if pl.words.ndim == base + 1:    # scanned layer stack
            return jnp.stack([
                materialize(SDVLinear(words=pl.words[i],
                                      scale=pl.scale[i], plan=pl.plan,
                                      d_out=pl.d_out), dtype)
                for i in range(pl.words.shape[0])])
        w_int = ref.sdv_unpack_words_ref(pl.words, plan=pl.plan)
        return (w_int[:, :pl.d_out].astype(jnp.float32)
                * pl.scale[None, :]).astype(dtype)
    per = 32 // pl.bits
    w, mask = pl.bits, (1 << pl.bits) - 1
    cols = []
    for i in range(per):
        f = (pl.words >> (i * w)) & mask
        f = jnp.where(f >= (1 << (w - 1)), f - (1 << w), f)
        cols.append(f)
    q = jnp.stack(cols, axis=-1)                 # [..., d_in, nw, per]
    full = q.reshape(q.shape[:-2] + (q.shape[-2] * per,))
    deq = full.astype(jnp.float32) * pl.scale
    return deq[..., :pl.d_out].astype(dtype)


def is_packed(x) -> bool:
    return isinstance(x, (PackedLinear, SDVLinear, BSEGConv))


def is_sdv(x) -> bool:
    return isinstance(x, SDVLinear)


_QUANT_LEAF_NAMES = ("kernel", "wi_gate", "wi_up", "wo")
_SKIP_CONTAINERS = ("router", "conv", "proj_patches")
#: top-level containers whose leading axis is the ``lax.scan`` layer
#: axis — a 3-D kernel under one of these is a *stack of 2-D GEMMs*
#: (scan slices the axis back off), so it is SDV-packable per layer;
#: a 3-D kernel anywhere else (an unstacked MoE expert bank) is a
#: genuinely 3-D einsum operand and keeps memory packing.
_STACKED_CONTAINERS = ("blocks", "groups", "tail", "enc_blocks",
                       "dec_blocks")


def _stacked_leading_axis(path: str) -> bool:
    head = path.split("/", 1)[0]
    return head in _STACKED_CONTAINERS or head.startswith("blocks_dense")


#: decode micro-batch rows the planner dimensions matmul layers for
PLANNER_DECODE_ROWS = 8


def serve_params(params: Any, bits: int = 4,
                 min_size: int = 1 << 16, compute: str = "memory",
                 act_bits: int = 8,
                 conv_bseg: Optional[bool] = None,
                 plan_policy: str = "default",
                 plan_cache: Optional[str] = None,
                 rows: Optional[int] = None) -> Any:
    """Rewrite a parameter *value* tree for quantized packed serving.

    ``compute="memory"`` packs every eligible kernel as ``PackedLinear``
    (HBM lane words); ``compute="sdv"`` packs 2-D kernels *and* scanned
    layer stacks of 2-D kernels (a 3-D leaf under a ``lax.scan``
    container — ``blocks``, ``groups``, ... — packs per layer with a
    shared plan) as ``SDVLinear`` (arithmetic packing — the GEMMs
    execute on the SDV datapath via ``packed_matmul``), keeping memory
    packing for unstacked >2-D expert banks, and — unless
    ``conv_bseg=False`` — the SSM/Griffin short-conv containers as
    ``BSEGConv`` (the convs execute on the BSEG datapath via the
    packed-conv dispatch).

    ``plan_policy`` selects the lane plans under ``compute="sdv"``:
    ``"default"`` keeps the uniform ``default_sdv_plan`` /
    ``default_bseg_plan``; ``"auto"`` searches per layer shape through
    the mixed-precision planner (``repro.planner``, DESIGN.md
    §Planner); ``"cache"`` additionally reuses/persists choices in the
    JSON plan cache at ``plan_cache`` (default ``$REPRO_PLAN_CACHE``).
    Any layer whose chosen plan would still land on the pure-jnp ref
    route is surfaced once per shape via ``warnings.warn`` instead of
    silently degrading.

    ``rows`` is the decode micro-batch row count the planner
    dimensions matmul layers for (default ``PLANNER_DECODE_ROWS``) —
    the serving engine passes each bucket's batch size so per-bucket
    plan resolution sees the shape it will actually run.

    ``params`` may be a ``LayerStream`` (memory packing only): its
    layers are then made and packed one at a time.
    """
    if compute not in ("memory", "sdv"):
        raise ValueError(f"unknown packed compute mode {compute!r}")
    if isinstance(params, LayerStream):
        return _serve_stream(params, bits, min_size, compute)
    if rows is None:
        rows = PLANNER_DECODE_ROWS
    if plan_policy not in ("default", "auto", "cache"):
        raise ValueError(f"unknown plan policy {plan_policy!r}")
    sdv_mode = compute == "sdv"
    if plan_policy != "default" and not sdv_mode:
        raise ValueError(
            f"plan_policy={plan_policy!r} plans arithmetic-packing "
            f"lane plans, which only exist under compute='sdv' — "
            f"memory packing has no plan to choose")
    # the uniform default plan is only *required* under the default
    # policy — the planner can still find a (possibly wider-datapath)
    # plan for bit configs the INT32 default cannot pack
    plan = default_sdv_plan(bits, act_bits) \
        if sdv_mode and plan_policy == "default" else None
    if conv_bseg is None:
        conv_bseg = sdv_mode
    conv_plan = default_bseg_plan(min(bits, 4)) if conv_bseg else None

    planner_ctx = None
    if plan_policy != "default" and sdv_mode:
        from repro import planner as _planner
        cache = _planner.PlanCache.load(plan_cache) \
            if plan_policy == "cache" else None
        planner_ctx = {"mod": _planner, "cache": cache, "memo": {},
                       "warned": set()}

    def _choose(layer):
        ctx = planner_ctx
        mk = layer.key()
        if mk not in ctx["memo"]:
            choice = None
            if ctx["cache"] is not None:
                choice = ctx["cache"].get_choice(layer)
            if choice is None:
                choice = ctx["mod"].choose_plan(layer)
                if ctx["cache"] is not None:
                    ctx["cache"].put_choice(choice, source="analytic")
            ctx["memo"][mk] = choice
        choice = ctx["memo"][mk]
        if choice.cost.route == "ref" and mk not in ctx["warned"]:
            ctx["warned"].add(mk)
            import warnings
            warnings.warn(
                f"serve_params: layer {layer.name!r} ({mk}) lands on "
                f"the pure-jnp ref route — {choice.cost.reason}",
                stacklevel=2)
        return choice.plan

    def layer_plan(name, v):
        """The SDV plan for one (possibly stacked) 2-D kernel leaf."""
        if planner_ctx is None:
            return plan
        layer = planner_ctx["mod"].matmul_spec(
            name, rows, v.shape[-2], v.shape[-1],
            w_bits=bits, a_bits=act_bits)
        return _choose(layer)

    def conv_layer_plan(name, w):
        """The BSEG plan for one short-conv container."""
        if planner_ctx is None:
            return conv_plan
        layer = planner_ctx["mod"].conv1d_spec(
            name, w.shape[-2], w.shape[-1], w_bits=min(bits, 4),
            a_bits=4, rows=rows)
        chosen = _choose(layer)
        return chosen if isinstance(chosen, BSEGPlan) else conv_plan

    def quantize(v, name="kernel"):
        if sdv_mode and (v.ndim == 2 or
                         (v.ndim == 3 and _stacked_leading_axis(name))):
            return pack_linear_sdv(v, layer_plan(name, v))
        return pack_linear(v, bits)

    def walk(tree, name):
        if isinstance(tree, dict):
            out = {}
            for k, v in tree.items():
                path = f"{name}/{k}" if name else k
                if k == "conv" and conv_plan is not None \
                        and isinstance(v, dict) and "w" in v \
                        and getattr(v["w"], "ndim", 0) in (2, 3):
                    out[k] = pack_conv_bseg(v, conv_layer_plan(path,
                                                               v["w"]))
                elif k in _SKIP_CONTAINERS:
                    out[k] = v
                elif isinstance(v, dict):
                    out[k] = walk(v, path)
                elif k in _QUANT_LEAF_NAMES and hasattr(v, "ndim") \
                        and v.ndim >= 2 and v.size >= min_size:
                    out[k] = quantize(v, path)
                else:
                    out[k] = v
            return out
        return tree

    out = walk(params, "")
    # the LM head is a plain array leaf at top level
    if isinstance(out, dict) and "lm_head" in out \
            and not is_packed(out["lm_head"]):
        out["lm_head"] = quantize(out["lm_head"], "lm_head")
    if planner_ctx is not None and planner_ctx["cache"] is not None:
        planner_ctx["cache"].save()
    return out


def serve_param_specs(shapes: Any, specs: Any, bits: int = 4,
                      min_size: int = 1 << 16) -> Any:
    """Mirror of ``serve_params`` over (ShapeDtypeStruct tree, spec
    tree): produces the PartitionSpec tree for the quantized layout.

    PackedLinear leaves keep the kernel's spec on ``words`` (dim names
    unchanged, minor dim shrinks by 32/bits — still TP-divisible thanks
    to 128-multiple output dims) and drop the reduced (second-to-last)
    axis from the ``scale`` spec.
    """
    from jax.sharding import PartitionSpec

    def scale_spec(spec, ndim):
        axes = list(spec) + [None] * (ndim - len(spec))
        axes[-2] = None
        return PartitionSpec(*axes)

    def quantized_leaf(shape_leaf, spec_leaf):
        per = 32 // bits
        d_out = shape_leaf.shape[-1]
        pad = (-d_out) % per
        nw = (d_out + pad) // per
        words = jax.ShapeDtypeStruct(shape_leaf.shape[:-1] + (nw,),
                                     jnp.int32)
        del words  # shape only needed for documentation
        return PackedLinear(words=spec_leaf,
                            scale=scale_spec(spec_leaf, shape_leaf.ndim),
                            bits=bits, d_out=d_out)

    def walk(sh, sp):
        if isinstance(sh, dict):
            out = {}
            for k in sh:
                if k in _SKIP_CONTAINERS:
                    out[k] = sp[k]
                elif isinstance(sh[k], dict):
                    out[k] = walk(sh[k], sp[k])
                elif k in _QUANT_LEAF_NAMES and hasattr(sh[k], "ndim") \
                        and sh[k].ndim >= 2 \
                        and int(np_prod(sh[k].shape)) >= min_size:
                    out[k] = quantized_leaf(sh[k], sp[k])
                else:
                    out[k] = sp[k]
            return out
        return sp

    out = walk(shapes, specs)
    if isinstance(out, dict) and "lm_head" in out \
            and not isinstance(out["lm_head"], PackedLinear):
        out["lm_head"] = quantized_leaf(shapes["lm_head"], specs["lm_head"])
    return out


def np_prod(shape) -> int:
    r = 1
    for s in shape:
        r *= int(s)
    return r
