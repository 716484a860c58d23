"""Grouped matmul over the experts of a dropless MoE layer.

The rows of one layer call (T tokens x top-k choices) arrive sorted by
expert; ``group_sizes[e]`` of them belong to expert ``e``, and each
is multiplied by its own expert's ``[k, n]`` matrix.  The kernel is
Megablox's (``jax.experimental.pallas.ops.tpu.megablox``): it walks
only the row tiles each expert owns, so no row is dropped and no row
is multiplied by an expert that did not choose it.

``moe_gmm`` is a jitted wrapper around Megablox's un-jitted body, so
the Pallas call sits directly inside it: the compiled op (and the
device trace) is then named ``moe_gmm``, as ``sdv_matmul`` names the
SDV kernel.  Called through Megablox's own jitted ``gmm`` it would be
named ``gmm``.
"""
from __future__ import annotations

import functools
import importlib

import jax
import jax.numpy as jnp

# the package's ``gmm`` attribute is the custom-VJP op; the module of
# the same name holds the forward body
_megablox = importlib.import_module(
    "jax.experimental.pallas.ops.tpu.megablox.gmm")

#: rows per tile (the MXU's edge); a layer call's rows pad up to it
TILE_M = 128


def _tile(dim: int, cap: int) -> int:
    """The largest of ``cap``, ``cap / 2``, ... (down to 128) that
    divides ``dim``, else ``dim`` itself (a small test shape)."""
    t = cap
    while t >= 128:
        if dim % t == 0:
            return t
        t //= 2
    return dim


def tiling(k: int, n: int) -> tuple:
    """(tm, tk, tn) for a ``[m, k] @ [k, n]`` group product: K tiles
    of up to 512 and N tiles of up to 1024 lanes (a granite expert's
    768-wide up projection takes one N tile)."""
    return (TILE_M, _tile(k, 512), n if n <= 1024 else _tile(n, 1024))


@functools.partial(jax.jit, static_argnames=("interpret",))
def moe_gmm(lhs: jnp.ndarray, rhs: jnp.ndarray, group_sizes: jnp.ndarray,
            *, interpret: bool) -> jnp.ndarray:
    """``lhs`` [m, k] rows sorted by group, ``rhs`` [E, k, n],
    ``group_sizes`` [E] int32 summing to m -> [m, n] in lhs's dtype
    (float32 accumulation).  Rows are padded to a multiple of
    ``TILE_M`` inside; the padding belongs to no group, its output is
    never written, and it is cut off before return."""
    m = lhs.shape[0]
    pad = (-m) % TILE_M
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    out = _megablox.gmm.__wrapped__(
        lhs, rhs.astype(lhs.dtype), group_sizes,
        preferred_element_type=lhs.dtype,
        tiling=tiling(rhs.shape[1], rhs.shape[2]), interpret=interpret)
    return out[:m]
