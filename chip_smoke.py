"""Chip smoke: the sort dataplane's main path, once, on a TPU.

Drives ``run_pipeline`` with the compiled whole-epoch device engine and the
run-arena server merge at 10M keys — the ``end_to_end`` setup of
``benchmarks/net_bench.py``: a binary tree fabric of height 3, 16 segments
of length 64, 4 egress servers — and checks every output byte against
numpy.  Phases, all in this one process (a chip belongs to one process):

* ``keys``: bare keys of the ``random`` trace; the Pallas bitonic block
  sort runs inside the epoch and the tournament kernel merges the runs.
* ``records``: the same keys with a 2-column int64 payload; the packed
  key+row records take the XLA lowering by the dtype rule of
  ``repro.kernels.ops``.
* ``--chips 4`` (and nothing else): the egress pool's distributed merge
  (``pool_backend="shard_map"``) on a 4-device mesh, compared byte for
  byte with ``pool_backend="numpy"``.

Each phase prints one JSON line: cold seconds (compilation included), warm
seconds, the lowering each sort/merge call took, the host<->device
transfers, and the device's peak bytes in use.  A phase fails the script if
its output differs from the reference, if a call ran in Pallas interpret
mode, or if anything but 64-bit keys took the XLA lowering.  The last line
is ``{"ok": true, "device": {...}}``, printed only when every phase passed.

    python chip_smoke.py [--n 10000000] [--seed 0] [--chips 4]

There is no CPU fallback: without a TPU the script exits non-zero.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

SMOKE = dict(
    topology="tree", branching=2, height=3,
    num_segments=16, segment_length=64, payload_size=256,
    num_flows=8, k=10, range_mode="oracle",
    num_servers=4, merge_backend="arena",
    engine="device", backend="pallas",
)


def _device_report(jax) -> dict:
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }


def _peak_bytes(jax) -> int | None:
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def _check_lowerings(counts: dict, phase: str) -> None:
    for key in counts:
        op, lowering, dtype = key.split(":")
        if lowering == "interpret":
            raise AssertionError(f"{phase}: {key} ran in Pallas interpret mode")
        if lowering == "xla" and dtype != "int64":
            raise AssertionError(f"{phase}: {key} took XLA, not the kernel")


def _timed_twice(jax, phase: str, run) -> tuple[object, dict]:
    """Run ``run`` cold then warm; return the warm result and the report."""
    from repro.kernels import ops
    from repro.net import device_epoch

    report: dict = {"phase": phase}
    for label in ("cold", "warm"):
        ops.reset_lowering_counts()
        device_epoch.reset_transfer_counts()
        t0 = time.perf_counter()
        res = run()
        jax.block_until_ready(res.output)
        report[f"{label}_s"] = time.perf_counter() - t0
        report[f"lowering_{label}"] = dict(ops.LOWERING_COUNTS)
        report[f"transfers_{label}"] = dict(device_epoch.TRANSFER_COUNTS)
        _check_lowerings(report[f"lowering_{label}"], phase)
    report["peak_bytes_in_use"] = _peak_bytes(jax)
    return res, report


def phase_keys(jax, run_pipeline, keys, maxv, seed) -> dict:
    res, report = _timed_twice(
        jax, "keys",
        lambda: run_pipeline(keys, max_value=maxv, seed=seed, **SMOKE),
    )
    np.testing.assert_array_equal(res.output, np.sort(keys))
    cold = report["lowering_cold"]
    if not cold.get("sort_rows_padded:pallas:int32"):
        raise AssertionError("keys: the block sort did not run the kernel")
    if not any(k.startswith("merge_tournament:pallas:") for k in cold):
        raise AssertionError("keys: the tournament did not run the kernel")
    return report


def phase_records(jax, run_pipeline, keys, maxv, seed) -> dict:
    payload = np.empty((keys.size, 2), dtype=np.int64)
    payload[:, 0] = keys * 7 + 3
    payload[:, 1] = np.arange(keys.size)
    res, report = _timed_twice(
        jax, "records",
        lambda: run_pipeline(
            keys, max_value=maxv, seed=seed, payload=payload, **SMOKE
        ),
    )
    np.testing.assert_array_equal(res.output, np.sort(keys))
    np.testing.assert_array_equal(
        res.sorted_payload, payload[np.argsort(keys, kind="stable")]
    )
    if not any(
        k.startswith("merge_tournament:xla:int64") for k in report["lowering_cold"]
    ):
        raise AssertionError("records: no packed int64 tournament ran")
    return report


def phase_pool(jax, run_pipeline, keys, maxv, seed) -> dict:
    from repro.distributed.sharding import pool_mesh

    mesh = pool_mesh(SMOKE["num_servers"])
    ids = [int(d.id) for d in mesh.devices.flat]
    if len(set(ids)) != 4:
        raise AssertionError(f"pool: mesh spans devices {ids}, not 4 chips")
    ref = run_pipeline(
        keys, max_value=maxv, seed=seed, **dict(SMOKE, pool_backend="numpy")
    )
    res, report = _timed_twice(
        jax, "pool_shard_map",
        lambda: run_pipeline(
            keys, max_value=maxv, seed=seed,
            **dict(SMOKE, pool_backend="shard_map"),
        ),
    )
    np.testing.assert_array_equal(res.output, ref.output)
    np.testing.assert_array_equal(res.output, np.sort(keys))
    if res.passes != ref.passes:
        raise AssertionError(f"pool: passes {res.passes} != {ref.passes}")
    report["mesh_device_ids"] = ids
    return report


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--n", type=int, default=10_000_000)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    src = Path(__file__).resolve().parent / "src"
    if not (src / "repro").is_dir():
        print(f"no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))

    import jax

    device = _device_report(jax)
    if device["platform"] != "tpu":
        print(f"no TPU: JAX reports {device}", file=sys.stderr)
        return 1
    if device["count"] < args.chips:
        print(f"--chips {args.chips} but JAX reports {device}", file=sys.stderr)
        return 1

    from repro.compile_cache import CACHE_EVENTS, enable_compile_cache
    from repro.data import TRACES, trace_max_value
    from repro.net import run_pipeline

    cache = enable_compile_cache()
    print(json.dumps({"device": device, "compile_cache": cache}), flush=True)
    keys = TRACES["random"](args.n, seed=args.seed)
    maxv = trace_max_value("random")
    phases = (phase_pool,) if args.chips == 4 else (phase_keys, phase_records)
    for phase in phases:
        report = phase(jax, run_pipeline, keys, maxv, args.seed)
        report["compile_cache"] = dict(CACHE_EVENTS)
        print(json.dumps(report), flush=True)
    if "repro.launch.dryrun" in sys.modules:
        raise AssertionError("repro.launch.dryrun (sets XLA_FLAGS) was imported")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
