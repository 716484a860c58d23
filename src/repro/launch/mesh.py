"""Production mesh construction + logical sharding rules.

``make_production_mesh`` is a function (not a module constant) so that
importing this module never touches JAX device state — the dry-run
launcher must set XLA_FLAGS before the first jax call.
"""
from __future__ import annotations

from typing import Dict

import jax
from jax.sharding import AxisType, NamedSharding, PartitionSpec

from repro.models.param import Rules


def make_mesh(shape, axes, *, devices=None):
    """``jax.make_mesh`` with every axis in automatic-sharding mode —
    the GSPMD propagation these programs are written for (jax's own
    default builds explicit-sharding axes, which demand an
    ``out_sharding`` on every gather)."""
    return jax.make_mesh(tuple(shape), tuple(axes),
                         axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def rules_for_mesh(mesh, *, fsdp: bool = False) -> Rules:
    """Logical->physical mapping for the given mesh."""
    names = mesh.axis_names
    batch = ("pod", "data") if "pod" in names else ("data",)
    tp_degree = mesh.shape["model"] if "model" in names else 1
    bdeg = 1
    for ax in batch:
        bdeg *= mesh.shape[ax]
    return Rules(
        tp="model" if "model" in names else None,
        fsdp="data" if fsdp and "data" in names else None,
        ep="model" if "model" in names else None,
        batch=batch,
        tp_degree=tp_degree,
        batch_degree=bdeg,
    )


def shardings_of(mesh, spec_tree):
    """PartitionSpec tree -> NamedSharding tree."""
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, PartitionSpec))


def batch_shardings(mesh, rules: Rules, batch_tree) -> Dict:
    """Shard every batch leaf along its leading (batch) axis."""
    def spec_for(x):
        nd = len(x.shape)
        lead = tuple(rules.batch) if rules.batch else None
        return NamedSharding(mesh,
                             PartitionSpec(lead, *([None] * (nd - 1))))
    return jax.tree_util.tree_map(spec_for, batch_tree)


# TPU v5e-class hardware model used by the roofline analysis
HW = {
    "peak_flops_bf16": 197e12,    # per chip
    "hbm_bw": 819e9,              # bytes/s per chip
    "ici_bw": 50e9,               # bytes/s per link
}
