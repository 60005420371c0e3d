"""Mesh construction.

Production single pod: v5e-256 as (data=16, model=16).
Multi-pod:  2 pods = 512 chips as (pod=2, data=16, model=16) — the ``pod``
axis carries only data parallelism + the federated upload/download
collectives (DCN-friendly), never tensor parallelism.
Serving: (data=1, model=N) — decode is latency-bound, so every device goes
to tensor parallelism; scale-out replicas are separate engine processes.

Functions, not module constants: importing this module must never touch JAX
device state (the dry-run sets XLA_FLAGS *before* the first jax import).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
from jax.sharding import AxisType, Mesh


def _auto_mesh(shape, axes, devices=None) -> Mesh:
    """``jax.make_mesh`` with Auto axes: the partition rules here pin
    layouts with sharding constraints and leave the rest to the compiler's
    propagation.  (``jax.make_mesh`` defaults to Explicit axes, under which
    every gather and scatter on a sharded array needs an out_sharding.)"""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_host_mesh(model: int = 1) -> Mesh:
    """Tiny mesh over however many real devices exist (tests / examples)."""
    n = len(jax.devices())
    return _auto_mesh((n // model, model), ("data", "model"))


def make_serve_mesh(model: int = 0, *, devices: Optional[Sequence] = None) -> Mesh:
    """Serving mesh (data=1, model=N) over the first N devices.

    ``model=0`` takes every device.  Parity tests build subset meshes of a
    forced 8-device host platform with ``model`` in {1, 2, 4, 8}.
    """
    devs = list(devices) if devices is not None else list(jax.devices())
    n = model or len(devs)
    if n > len(devs):
        raise ValueError(f"requested model={n} but only {len(devs)} devices")
    return _auto_mesh((1, n), ("data", "model"), devices=devs[:n])


def make_fed_mesh(data: int = 0, *, devices: Optional[Sequence] = None) -> Mesh:
    """Federated simulation mesh (data=N, model=1) over the first N devices.

    The data axis carries the cohort's client dimension (see
    :mod:`repro.topology.fed`); ``model`` is kept (size 1) so fed specs and
    training specs share the same axis vocabulary.  ``data=0`` takes every
    device.
    """
    devs = list(devices) if devices is not None else list(jax.devices())
    n = data or len(devs)
    if n > len(devs):
        raise ValueError(f"requested data={n} but only {len(devs)} devices")
    return _auto_mesh((n, 1), ("data", "model"), devices=devs[:n])


def data_axes(mesh: Mesh):
    """Axes carrying the batch dimension."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def axis_size(mesh, name: str) -> int:
    """Axis size by name (1 for absent axes).

    Reads ``mesh.shape`` — the name→size mapping shared by ``Mesh`` and
    ``jax.sharding.AbstractMesh`` — so partition rules can be validated
    abstractly (the contract checker builds device-free meshes).
    """
    return dict(mesh.shape).get(name, 1)
