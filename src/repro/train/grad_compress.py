"""Int8 gradient all-reduce with error feedback, SDV-packed on the wire.

The paper packs low-bit values onto wide datapaths; the same idea
applied to the *interconnect* shrinks gradient all-reduce bytes.
Protocol (inside shard_map over the reduction axes):

  1. g' = g + e            (add the residual from the previous step)
  2. s  = psum-max(|g'|) / 127     (shared scale, one scalar per tensor)
  3. q  = round(g'/s) int8, then SDV-pack PAIRS of int8 values into one
     int32 word via ``core/signed_split.pack_signed`` (16-bit lanes:
     word = v0 + 2^16 v1, the pre-adder D - A form) and all-reduce the
     WORDS — summing packed words sums every lane independently, the
     paper's Eq. 4 linearity, so one int32 word on the wire carries two
     int8 gradients (2 bytes/element vs 4 for the int32-per-element
     reduce).  Lane sums stay in signed 16 bits up to
     ``MAX_PACKED_DEVICES`` devices; beyond that the unpacked int32
     reduce is used automatically.
  4. decode lanes low-to-high with borrow (exact), g_hat = q_sum * s /
     n_dev ; e = g' - dequant(own q)   (feedback)

Exact all-reduce of the quantized values — packing is algebraically
lossless (``tests/test_qat.py`` pins packed == unpacked bitwise); the
only loss is the quantization itself, which error feedback pushes to
O(1/steps).
"""
from __future__ import annotations

from typing import Any, Optional, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as PS

from repro.core import signed_split

#: bits per lane of the packed gradient word (two lanes per int32)
GRAD_LANE = 16
#: devices whose +/-127 lane contributions still fit a signed 16-bit
#: lane sum: 127 * 258 = 32766 <= 2^15 - 1 (and the int32 word total
#: 127 * 65537 * 258 stays under 2^31)
MAX_PACKED_DEVICES = 258


def pack_grad_words(q: jnp.ndarray) -> jnp.ndarray:
    """int8-valued [...]-shaped q -> int32 SDV words [ceil(size/2)].

    Flattens, zero-pads to an even count, and packs value pairs
    through the pre-adder form (``pack_signed``: D - A with 16-bit
    lanes) — int32-only, x64-free."""
    flat = q.reshape(-1).astype(jnp.int32)
    if flat.shape[0] % 2:
        flat = jnp.pad(flat, (0, 1))
    pairs = flat.reshape(-1, 2)
    return signed_split.pack_signed(pairs, GRAD_LANE, GRAD_LANE,
                                    jnp.int32)


def unpack_grad_words(words: jnp.ndarray, size: int) -> jnp.ndarray:
    """Decode summed words back to per-element lane sums [size] i32.

    Low-to-high with borrow: the low lane is recovered mod 2^16 into
    the signed 16-bit range (exact while lane sums fit — the
    ``MAX_PACKED_DEVICES`` bound), then subtracted off so the
    arithmetic shift yields the high lane exactly."""
    half = 1 << (GRAD_LANE - 1)
    mask = (1 << GRAD_LANE) - 1
    v0 = ((words + half) & mask) - half
    v1 = (words - v0) >> GRAD_LANE
    return jnp.stack([v0, v1], axis=-1).reshape(-1)[:size]


def compress_psum(g: jnp.ndarray, err: jnp.ndarray, axes: Sequence[str],
                  pack_words: bool = True):
    """Inside-shard_map int8 all-reduce with error feedback.

    ``pack_words`` reduces SDV-packed int32 words (two int8 values per
    word — half the wire bytes); the caller must guarantee the total
    device count over ``axes`` is <= ``MAX_PACKED_DEVICES``
    (``compressed_allreduce`` checks).  Packed and unpacked paths are
    bit-exact equals.

    Returns (g_hat mean-reduced, new_err)."""
    gf = g.astype(jnp.float32) + err
    amax = jnp.max(jnp.abs(gf))
    amax = jax.lax.pmax(amax, axes[0])
    for a in axes[1:]:
        amax = jax.lax.pmax(amax, a)
    scale = jnp.maximum(amax, 1e-12) / 127.0
    q = jnp.clip(jnp.round(gf / scale), -127, 127).astype(jnp.int8)
    deq_local = q.astype(jnp.float32) * scale
    new_err = gf - deq_local
    if pack_words:
        red = pack_grad_words(q)
    else:
        red = q.astype(jnp.int32)
    red = jax.lax.psum(red, axes[0])
    for a in axes[1:]:
        red = jax.lax.psum(red, a)
    if pack_words:
        qsum = unpack_grad_words(red, g.size).reshape(g.shape)
    else:
        qsum = red
    n = 1
    for a in axes:
        n *= jax.lax.axis_size(a)
    g_hat = (qsum.astype(jnp.float32) * scale / n).astype(g.dtype)
    return g_hat, new_err


def compressed_allreduce(grads: Any, errs: Any, mesh,
                         axis: str = "data",
                         pack_words: Optional[bool] = None):
    """shard_map wrapper for testing/driving the protocol end to end.

    ``grads``/``errs`` leaves are stacked per-device local values with a
    leading axis of size mesh.shape[axis], sharded along ``axis``.
    ``pack_words=None`` packs whenever the device count allows it.
    Returns (mean-reduced g_hat, replicated; per-device new errors)."""
    n_dev = int(mesh.shape[axis])
    if pack_words is None:
        pack_words = n_dev <= MAX_PACKED_DEVICES
    elif pack_words and n_dev > MAX_PACKED_DEVICES:
        raise ValueError(
            f"packed gradient all-reduce overflows 16-bit lane sums at "
            f"{n_dev} devices (max {MAX_PACKED_DEVICES})")

    def body(g_tree, e_tree):
        flat_g, tdef = jax.tree_util.tree_flatten(g_tree)
        flat_e = jax.tree_util.tree_flatten(e_tree)[0]
        outs = [compress_psum(g[0], e[0], (axis,), pack_words=pack_words)
                for g, e in zip(flat_g, flat_e)]
        gh = jax.tree_util.tree_unflatten(tdef, [o[0] for o in outs])
        ne = jax.tree_util.tree_unflatten(tdef, [o[1][None] for o in outs])
        return gh, ne

    in_spec = jax.tree_util.tree_map(lambda _: PS(axis), grads)
    out_spec = (jax.tree_util.tree_map(lambda _: PS(), grads),
                jax.tree_util.tree_map(lambda _: PS(axis), grads))
    return jax.shard_map(body, mesh=mesh, in_specs=(in_spec, in_spec),
                         out_specs=out_spec, check_vma=False)(grads, errs)
