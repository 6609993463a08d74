"""The host↔device copies of a job's data, made and counted in one place.

Every copy between the host and the device goes through :func:`to_device`
or :func:`fetch`, so :data:`TRANSFER_COUNTS` says how many a run made: one
each way per fabric epoch (:mod:`repro.net.device_epoch`) and one each way
per run-arena tournament (:mod:`repro.core.mergesort`).  ``jax`` is
imported at the first copy.
"""

from __future__ import annotations

#: Copies made since the last :func:`reset_transfer_counts`.
TRANSFER_COUNTS = {"to_device": 0, "to_host": 0}


def reset_transfer_counts() -> None:
    TRANSFER_COUNTS["to_device"] = 0
    TRANSFER_COUNTS["to_host"] = 0


def nbytes(tree) -> int:
    """Bytes held by the arrays of a pytree (host or device arrays)."""
    import jax

    return sum(int(x.nbytes) for x in jax.tree_util.tree_leaves(tree))


def to_device(tree):
    """Copy a pytree of host arrays to the default device."""
    import jax

    TRANSFER_COUNTS["to_device"] += 1
    return jax.device_put(tree)


def fetch(tree):
    """Copy a pytree of device arrays to the host, as numpy arrays."""
    import jax

    TRANSFER_COUNTS["to_host"] += 1
    return jax.device_get(tree)
