import os

os.environ.setdefault(
    "XLA_FLAGS", "--xla_force_host_platform_device_count=512"
)

"""The paper's own workload at pod scale: lower + compile the distributed
range sort (core/distributed.py) on the production mesh and report its
roofline terms — the 256 chips are the switch's segments, ICI the fabric.

    PYTHONPATH=src:. python -m benchmarks.sort_dryrun [--per-chip 16777216]
"""

import argparse
import math

try:
    import _bootstrap  # noqa: F401  (python benchmarks/sort_dryrun.py)
except ImportError:  # pragma: no cover - python -m benchmarks.sort_dryrun
    from benchmarks import _bootstrap  # noqa: F401

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

try:
    from benchmarks.hlo_analysis import analyze_text
    from benchmarks.roofline import HBM_BW, ICI_BW, PEAK_FLOPS
except ImportError:  # run as a plain script: benchmarks/ is sys.path[0]
    from hlo_analysis import analyze_text
    from roofline import HBM_BW, ICI_BW, PEAK_FLOPS
from repro.core.distributed import _sort_body
from repro.launch.mesh import make_production_mesh

import functools

from jax import shard_map


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--per-chip", type=int, default=16 * 2**20,
                    help="values per chip (default 16M -> 4G total)")
    ap.add_argument("--presort-block", type=int, default=256)
    args = ap.parse_args()

    mesh = make_production_mesh()  # (data=16, model=16) = 256 chips
    chips = math.prod(mesh.shape.values())
    n = args.per_chip * chips
    axis = ("data", "model")  # flatten the whole pod into segments
    capacity = int(args.per_chip / chips * 2.0)
    capacity = -(-capacity // args.presort_block) * args.presort_block

    body = functools.partial(
        _sort_body,
        axis_name=axis,
        num_devices=chips,
        capacity=capacity,
        presort_block=args.presort_block,
    )
    shmapped = shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=(P(axis), P(axis), P(axis)),
    )
    x = jax.ShapeDtypeStruct((n,), jnp.int32)
    splits = jax.ShapeDtypeStruct((chips - 1,), jnp.int32)
    lowered = jax.jit(shmapped).lower(x, splits)
    compiled = lowered.compile()
    st = analyze_text(compiled.as_text())
    mem = compiled.memory_analysis()
    hbm = (mem.argument_size_in_bytes + mem.temp_size_in_bytes
           + mem.output_size_in_bytes - mem.alias_size_in_bytes)
    compute_s = st.flops / PEAK_FLOPS
    memory_s = st.hbm_bytes / HBM_BW
    coll_s = st.collective_bytes / ICI_BW
    print(f"distributed sort: {n/2**30:.1f} Gvalues over {chips} chips")
    print(f"  compute_s {compute_s:.4f}  memory_s {memory_s:.4f}  "
          f"collective_s {coll_s:.4f}  (dominant: "
          f"{max([('compute',compute_s),('memory',memory_s),('collective',coll_s)], key=lambda kv: kv[1])[0]})")
    print(f"  hbm/chip {hbm/2**30:.2f} GiB  "
          f"a2a bytes/chip {st.per_collective['all-to-all']['bytes']/2**20:.1f} MiB")
    # the paper's metric: values/s at the collective bound
    bound = max(compute_s, memory_s, coll_s)
    print(f"  => >= {n/bound/1e9:.1f} Gvalues/s pod throughput at the "
          f"roofline bound ({bound*1e3:.2f} ms/pass)")


if __name__ == "__main__":
    main()
