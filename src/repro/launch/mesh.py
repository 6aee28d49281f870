"""Production mesh construction.

A FUNCTION, not a module-level constant, so importing this module never
touches jax device state (smoke tests must keep seeing 1 CPU device; only
dryrun.py sets the 512-placeholder-device XLA flag).
"""
from __future__ import annotations

import jax


def shard_map_compat(f, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication check off: callers here mix
    collectives in ways the static checker rejects."""
    return jax.shard_map(
        f, mesh=mesh, in_specs=in_specs, out_specs=out_specs, check_vma=False,
    )


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with every axis ``Auto``.

    ``jax.make_mesh`` defaults to ``Explicit`` axes, which
    ``with_sharding_constraint`` (the ``shard_hint`` activation
    constraints and the pod-mesh batch placement) refuses; every mesh in
    this repo is built here so those constraints stay legal.
    """
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh with the production axis names (CPU tests)."""
    return make_mesh((1, 1), ("data", "model"))


def make_federated_mesh(*, multi_pod: bool = False):
    """Mesh for the hierarchical-FL runtime: the `data` axis carries
    federated clients; pods play the fog-cluster role (DESIGN.md §3)."""
    return make_production_mesh(multi_pod=multi_pod)


# TPU v5e hardware constants (roofline targets; this container is CPU-only).
PEAK_FLOPS_BF16 = 197e12       # per chip
HBM_BW = 819e9                 # bytes/s per chip
ICI_BW = 50e9                  # bytes/s per link
