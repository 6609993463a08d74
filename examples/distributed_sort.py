"""Pod-scale distributed range sort — the paper's switch fabric on a mesh.

Devices along one mesh axis play the switch's pipeline segments (one key
range each); the all_to_all over ICI is the fabric; per-device local sort is
the segment pipeline; host-side concatenation by device order is the server.

Runs on 8 fake CPU devices (the same shard_map code runs unchanged on a
real pod axis).

    PYTHONPATH=src python examples/distributed_sort.py
"""

import os

os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=8")

import time

import _bootstrap  # noqa: F401
import jax
import jax.numpy as jnp
import numpy as np

from repro.core.distributed import gather_sorted, make_splitters, sort_sharded
from repro.distributed.sharding import make_mesh
from repro.core.runs import RunStats
from repro.data import network_trace


def main() -> None:
    D = 8
    mesh = make_mesh((D,), ("segments",))
    x = network_trace(D * 131_072).astype(np.int32)
    print(f"sorting {x.size} values across {D} devices "
          f"({RunStats.of(x).num_runs} runs in input)")

    # control plane: balanced splitters from a sample (the paper computes
    # ranges at the server because the data plane cannot divide)
    splitters = make_splitters(x[:: 97], D)

    t0 = time.perf_counter()
    padded, valid, overflow = sort_sharded(
        jnp.asarray(x), mesh, "segments", splitters,
        capacity_factor=2.0, presort_block=256,
    )
    jax.block_until_ready(padded)
    dt = time.perf_counter() - t0
    assert int(overflow.sum()) == 0, "splitter imbalance"
    out = gather_sorted(np.asarray(padded), np.asarray(valid))
    np.testing.assert_array_equal(out, np.sort(x))
    print(f"device counts: {np.asarray(valid).ravel().tolist()}")
    print(f"sorted + verified in {dt:.3f}s "
          f"({RunStats.of(out).num_runs} run == fully sorted)")


if __name__ == "__main__":
    main()
