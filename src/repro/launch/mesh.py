"""Production mesh construction.

IMPORTANT: functions, not module-level constants — importing this module must
never touch jax device state (the dry-run sets the fake-device XLA flag
before first jax init; smoke tests must keep seeing 1 device).
"""

from __future__ import annotations

from ..distributed.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (TPU v5e pod).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips across DCN."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)
