"""Distributed range sort — the paper's switch fabric mapped onto a TPU mesh.

Mapping (DESIGN.md §2): devices along one mesh axis play the switch's pipeline
segments, each owning one key range; the ``all_to_all`` over ICI is the
switch fabric the data would traverse anyway; the per-device local sort is
the segment's compare-exchange pipeline; concatenation by device order is the
server's final concatenation.  The control plane (host) computes the range
splitters — the paper makes the same split because the data plane cannot
divide.

Everything here is pure ``shard_map`` + ``jax.lax`` collectives and runs
unchanged on any mesh axis (single-pod ``model`` axis, or a flattened
``("pod","data","model")`` axis at 512 chips).
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax import shard_map


def blockwise_sort_jax(x: jax.Array, block: int) -> jax.Array:
    """JAX MergeMarathon segment emission: sort consecutive ``block`` chunks.

    Requires ``x.shape[-1] % block == 0`` (pad with +inf sentinels first if
    needed).  Equals the faithful switch output (marathon.py equivalence).
    """
    *lead, n = x.shape
    if n % block:
        raise ValueError(f"length {n} not divisible by block {block}")
    xb = x.reshape(*lead, n // block, block)
    return jnp.sort(xb, axis=-1).reshape(*lead, n)


def _sentinel(dtype) -> Any:
    if jnp.issubdtype(dtype, jnp.integer):
        return jnp.iinfo(dtype).max
    return jnp.inf


def make_splitters(sample: np.ndarray, num_devices: int) -> np.ndarray:
    """Control plane: balanced splitters from a host-side sample."""
    qs = np.quantile(np.asarray(sample), np.linspace(0, 1, num_devices + 1)[1:-1])
    return np.asarray(qs)


def _sort_body(
    xl: jax.Array,
    splits: jax.Array,
    *,
    axis_name: str,
    num_devices: int,
    capacity: int,
    presort_block: int | None,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-device body: route → exchange → local sort."""
    (n,) = xl.shape
    sent = _sentinel(xl.dtype)
    # -- route: which range segment (device) owns each local value --------
    bucket = jnp.searchsorted(splits, xl, side="right")  # (n,) in [0, D)
    order = jnp.argsort(bucket, stable=True)
    sb = bucket[order]
    # rank of each element within its bucket
    first_of_group = jnp.searchsorted(sb, sb, side="left")
    rank = jnp.arange(n) - first_of_group
    send = jnp.full((num_devices, capacity), sent, dtype=xl.dtype)
    send = send.at[sb, rank].set(xl[order], mode="drop")
    counts = jnp.bincount(bucket, length=num_devices)
    overflow = jnp.maximum(counts - capacity, 0).sum()
    # -- on-path partial sort (MergeMarathon): pre-sort each send chunk ---
    if presort_block is not None:
        send = blockwise_sort_jax(send, presort_block)
    # -- the fabric: all_to_all over ICI ----------------------------------
    recv = jax.lax.all_to_all(send, axis_name, 0, 0, tiled=True)
    # -- segment-local sort; sentinels sort to the end ---------------------
    out = jnp.sort(recv.reshape(-1))
    valid = (out != sent).sum()
    # rank-0 per-device scalars get a singleton axis so shard_map can
    # concatenate them along the mesh axis
    return out, valid[None], overflow[None]


def sort_sharded(
    x: jax.Array,
    mesh: Mesh,
    axis_name: str,
    splitters: jax.Array | np.ndarray,
    capacity_factor: float = 2.0,
    presort_block: int | None = None,
):
    """Globally sort ``x`` (sharded over ``axis_name``).

    Returns ``(padded, valid, overflow)``: per-device sorted chunks (padded
    with the dtype's max sentinel), the per-device valid counts, and the
    number of values dropped due to capacity overflow (0 in healthy runs —
    monitored and used to trigger splitter rebalancing upstream).
    Concatenating ``padded[d, :valid[d]]`` in device order is the sorted
    stream.
    """
    num_devices = mesh.shape[axis_name]
    n_local = x.shape[0] // num_devices
    capacity = int(np.ceil(n_local / num_devices * capacity_factor))
    if presort_block is not None:
        # pad capacity to a multiple of the presort block
        capacity = -(-capacity // presort_block) * presort_block
    splitters = jnp.asarray(splitters, dtype=x.dtype)

    body = functools.partial(
        _sort_body,
        axis_name=axis_name,
        num_devices=num_devices,
        capacity=capacity,
        presort_block=presort_block,
    )
    shmapped = shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis_name), P()),
        out_specs=(P(axis_name), P(axis_name), P(axis_name)),
    )
    fn = jax.jit(shmapped)
    padded, valid, overflow = fn(x, splitters)
    return (
        padded.reshape(num_devices, -1),
        valid,
        overflow,
    )


def gather_sorted(padded: np.ndarray, valid: np.ndarray) -> np.ndarray:
    """Host-side concatenation by device (segment) order."""
    return np.concatenate(
        [np.asarray(padded[d, : int(valid[d])]) for d in range(padded.shape[0])]
    )


# ---------------------------------------------------------------------------
# Egress server-pool merge (repro.net.egress.ServerPool)
# ---------------------------------------------------------------------------


def pool_concat_sharded(
    outs: list[np.ndarray], mesh: Mesh, axis_name: str = "server"
) -> np.ndarray:
    """Distributed concatenation of per-server sorted range shards.

    Server ``s``'s shard is padded to the pool-wide capacity with the
    dtype-max sentinel and the ``S`` shards are split in order over the
    ``d`` devices of a one-axis mesh (``d`` divides ``S``); one tiled
    ``all_gather`` inside ``shard_map`` moves every shard to every device —
    the paper's "concatenate" executed as the collective the pod fabric
    would use — and the host compacts by the true shard lengths
    (:func:`gather_sorted`), so sentinel collisions with real keys are
    harmless.
    """
    num_servers = len(outs)
    if num_servers % mesh.shape[axis_name]:
        raise ValueError(
            f"{num_servers} shards do not split over a "
            f"{mesh.shape[axis_name]}-device {axis_name!r} axis"
        )
    valid = np.array([o.size for o in outs], dtype=np.int64)
    cap = int(valid.max())
    if cap == 0:
        return np.zeros(0, dtype=np.int64)
    padded = np.full((num_servers, cap), np.iinfo(np.int64).max, dtype=np.int64)
    for s, o in enumerate(outs):
        padded[s, : o.size] = o
    from ..kernels.ops import x64_scope

    fn = _pool_gather(mesh, axis_name)
    with x64_scope():  # int64 keys: no truncation at the device boundary
        gathered = np.asarray(
            jax.device_get(
                fn(jax.device_put(padded, NamedSharding(mesh, P(axis_name, None))))
            )
        )
    return gather_sorted(gathered, valid)


# The jitted gather is cached per mesh so repeated merges hit the jit cache
# (a fresh closure per call would retrace inside the pool's timed merge
# span); jit itself re-specializes when the shard capacity changes.
_POOL_GATHER_CACHE: dict = {}


def _pool_gather(mesh: Mesh, axis_name: str):
    key = (mesh, axis_name)
    fn = _POOL_GATHER_CACHE.get(key)
    if fn is None:

        def body(xl: jax.Array) -> jax.Array:
            return jax.lax.all_gather(xl, axis_name, axis=0, tiled=True)

        fn = jax.jit(
            shard_map(
                body,
                mesh=mesh,
                in_specs=(P(axis_name, None),),
                out_specs=P(None, None),
                # all_gather output IS replicated over the axis; the static
                # checker can't always prove it (see sharding.fsdp_gather)
                check_vma=False,
            )
        )
        _POOL_GATHER_CACHE[key] = fn
    return fn


def pool_concat(
    outs: list[np.ndarray],
    *,
    disjoint: bool,
    backend: str = "numpy",
    mesh: Mesh | None = None,
    axis_name: str = "server",
) -> np.ndarray:
    """Merge per-server egress-pool outputs into the global sorted stream.

    ``disjoint=True`` (one control-plane epoch: server order is key-range
    order) concatenates — on the host, or with ``backend="shard_map"`` via
    :func:`pool_concat_sharded` over ``mesh`` (built on demand over the
    devices present by :func:`repro.distributed.sharding.pool_mesh`).
    ``disjoint=False``
    (epoched re-partitioning: server ranges overlap) k-way merges the
    sorted server streams — inherently sequential, always on the host.
    """
    # Wide records (rows of int32 sort words) keep their dtype.
    outs = [o if np.ndim(o) == 2 else np.asarray(o, dtype=np.int64)
            for o in outs]
    if not outs:
        return np.zeros(0, dtype=np.int64)
    if len(outs) == 1:
        return outs[0]
    if not disjoint:
        from .mergesort import merge_runs

        nonempty = [o for o in outs if o.size]
        return merge_runs(nonempty) if nonempty else np.zeros(0, dtype=np.int64)
    if backend == "shard_map":
        if mesh is None:
            from ..distributed.sharding import pool_mesh

            mesh = pool_mesh(len(outs), axis_name)
        return pool_concat_sharded(outs, mesh, axis_name)
    return np.concatenate(outs)
