"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — the dry-run must set XLA_FLAGS before first init.
"""
from __future__ import annotations

import jax


def make_mesh(axis_shapes, axis_names, *, devices=None):
    """``jax.make_mesh`` with every axis ``Auto`` (sharding propagated by
    GSPMD).  All mesh construction in the repo (and the multidevice tests'
    subprocess bodies) routes through here."""
    return jax.make_mesh(
        axis_shapes, axis_names,
        axis_types=(jax.sharding.AxisType.Auto,) * len(axis_names),
        devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """v5e pod: 16×16 = 256 chips; multi-pod: 2 pods = 512 chips.

    The ``pod`` axis extends data parallelism hierarchically (gradient
    all-reduce crosses pods once per step, optionally int8-compressed —
    see repro.distributed.compression).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh():
    """Single-device mesh for tests/examples on CPU."""
    return make_mesh((1, 1), ("data", "model"))
