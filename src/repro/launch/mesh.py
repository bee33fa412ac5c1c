"""Production meshes.

Defined as FUNCTIONS (not module constants) so importing this module never
touches jax device state — the dry-run must set
XLA_FLAGS=--xla_force_host_platform_device_count=512 before the first jax
device query, and smoke tests must keep seeing 1 device.

Axes:
  pod   — CoRS client axis (multi-pod only). Gradients are never reduced
          over it; the paper's representation exchange is its only traffic.
  data  — batch / FSDP axis.
  model — tensor-parallel axis.
"""
from __future__ import annotations

from repro.sharding import auto_mesh


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh():
    """1-device mesh for CPU smoke runs (same axis names, sizes 1)."""
    return auto_mesh((1, 1), ("data", "model"))


def describe(mesh) -> str:
    return "x".join(f"{n}:{mesh.shape[n]}" for n in mesh.axis_names)
