"""Chip benchmark of the sort dataplane: one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload keys_random --seed 7 --seconds 51 --trace 0

A cell names a configuration (``bench/configs/<config>.json``: the job's
size, the fabric, the guarantees) and a traffic mix
(``bench/traffic/<traffic>.json``).  The configuration's ``relation``
names what a job is and what it answers, ``int_keys`` where it names none:
the module ``bench/relations/<relation>.py`` draws the relation from the
traffic's parameters, makes the one timed call and builds the plain
reference.  The cell's own file, ``bench/workloads/<cell>.json``, holds
the limit of each number that decides ``correct``.  A per-layer metric is
a reader, ``bench/metrics/<name>.py``.  This file names no cell,
configuration, relation or metric beyond that default: a new one is new
files and ``BENCHMARK.json`` entries.

Set-up: draw the relation from ``--seed``, build its reference, then run
one warm-up job, which compiles, or loads from JAX's persistent cache,
every program the window runs.  The window, with ``--trace 0``, is a
closed loop with one client: jobs run one after another, each on a fresh
copy of the same relation made outside the clock, and start while their
summed time is below ``--seconds``.  A job is one
``repro.net.run_pipeline`` call, from the relation on the host to the
sorted relation on the host.  With ``--trace 1`` two jobs run and the JAX
profiler records the second, and the per-layer metrics are read from its
trace.

The reference's build is left out of ``setup_s``.  Each job's answer is
compared with it right after the job, outside the clock, so that the
process holds one answer at a time; only the counts are kept.  Once the
window has closed, the device's peak memory is read.  The last line of
standard output is the result as JSON.  Without a TPU, with fewer chips
than the cell asks for, or when any sort or merge ran in Pallas interpret
mode, the run exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

import traffic  # noqa: E402
import tracefile  # noqa: E402

#: JAX's monitoring event of one program compiled, or loaded from the
#: persistent cache, for a new shape.
_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"

#: JAX's persistent compilation cache: one fixed directory inside the
#: checkout (git-ignored), whatever the environment names, so that only a
#: checkout's first run of a cell compiles and two checkouts share nothing.
CACHE_DIR = ROOT / ".jax_cache"


def _read_json(path: Path):
    return json.loads(path.read_text())


@dataclasses.dataclass
class Cell:
    """One ``workloads`` entry of ``BENCHMARK.json`` with its files read."""

    name: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str) -> Cell:
    spec = _read_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    files = {c["name"]: c["file"] for c in spec["configs"]}
    params = traffic.load(w["traffic"])
    if (params.get("loop"), params.get("clients")) != ("closed", 1):
        raise SystemExit(
            f"traffic {w['traffic']!r}: only a closed loop with one client "
            "is supported"
        )
    return Cell(
        name=name,
        chips=int(w["chips"]),
        config=_read_json(ROOT / files[w["config"]]),
        traffic=params,
        limits=_read_json(BENCH / "workloads" / f"{name}.json")["limits"],
        end_to_end=[m for m in spec["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in spec["per_layer"] if _applies(m, name)],
    )


def require_chip(chips: int) -> None:
    """Exit non-zero unless JAX sees at least ``chips`` TPUs."""
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        raise SystemExit(f"no TPU: JAX reports {devices[0].platform}")
    if len(devices) < chips:
        raise SystemExit(
            f"the cell asks for {chips} chips, JAX sees {len(devices)}"
        )


def enable_cache() -> None:
    """Turn on the program's persistent compilation cache, in ``CACHE_DIR``."""
    import jax

    from repro.compile_cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))


def require_kernels(lowering: dict) -> None:
    """Exit non-zero if a sort or merge ran in Pallas interpret mode."""
    slow = sorted(k for k in lowering if ":interpret:" in k)
    if slow:
        raise SystemExit(f"Pallas interpret mode ran: {slow}")


def _program_files() -> frozenset:
    """File names of the program's modules, which label idle gaps."""
    return frozenset(p.name for p in (ROOT / "src").rglob("*.py"))


def _load_module(kind: str, name: str):
    """The module ``bench/<kind>/<name>.py``, loaded by its path."""
    path = BENCH / kind / f"{name}.py"
    spec = importlib.util.spec_from_file_location(f"bench_{kind}_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[spec.name] = mod  # dataclasses look their module up here
    spec.loader.exec_module(mod)
    return mod


def load_reader(name: str):
    return _load_module("metrics", name).read


def load_relation(cell: Cell):
    """The cell's relation module, ``bench/relations/<kind>.py``: the
    configuration's ``relation``, ``int_keys`` where it names none."""
    return _load_module("relations", cell.config.get("relation", "int_keys"))


def _device_report() -> dict:
    import jax

    devices = jax.devices()
    peaks = [
        (d.memory_stats() or {}).get("peak_bytes_in_use") for d in devices
    ]
    peak = max((p for p in peaks if p is not None), default=None)
    return {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
        "memory_peak_bytes": peak,
    }


class Judge:
    """Compares each job's answer with the reference as it comes, and keeps
    only the counts: the jobs attempted and failed and, per number, the
    worst value over the jobs."""

    def __init__(self, cell: Cell, ref):
        self.cell, self.ref = cell, ref
        self.attempted = self.failed = 0
        self.worst: dict[str, int] = {}

    def __call__(self, answer) -> None:
        nums = self.ref.compare(answer)
        limits = self.cell.limits
        missing = sorted(set(nums) - set(limits))
        if missing:
            raise SystemExit(
                f"no limit for {missing} in workloads/{self.cell.name}.json"
            )
        self.attempted += 1
        self.failed += any(v > limits[k] for k, v in nums.items())
        for k, v in nums.items():
            self.worst[k] = max(self.worst.get(k, v), v)

    def checks(self) -> dict:
        limits = self.cell.limits
        return {
            k: {"value": v, "limit": limits[k]} for k, v in self.worst.items()
        }


def run_cell(cell: Cell, seed: int, seconds: float, trace: bool,
             *, check_chip: bool = True) -> dict:
    """Set up, run the window and judge it; return the result line.

    ``check_chip=False`` skips the checks that the run is on the chip's
    compiled path (platform, chip count, no interpret lowering), so that a
    test can drive the rest of a run on the CPU.
    """
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    sys.path.insert(0, str(ROOT / "src"))
    if check_chip:
        require_chip(cell.chips)
    import jax

    enable_cache()
    compiles: collections.Counter = collections.Counter()

    def on_duration(event: str, *_args, **_kw) -> None:
        if event == _COMPILE_EVENT:
            compiles["programs"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        return _run(cell, seed, seconds, trace, check_chip, compiles)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)


def _run(cell, seed, seconds, trace, check_chip, compiles) -> dict:
    import jax

    from repro.compile_cache import CACHE_EVENTS
    from repro.kernels import ops
    from repro.net import device_epoch

    work = load_relation(cell).Workload(cell, seed)
    t0 = time.perf_counter()
    judge = Judge(cell, work.reference())
    reference_s = time.perf_counter() - t0
    warm_s, _ = work.job()
    if check_chip:
        require_kernels(ops.LOWERING_COUNTS)
    setup_s = time.perf_counter() - T_START - reference_s
    setup_counts = {
        "lowering": dict(ops.LOWERING_COUNTS),
        "compile_cache": dict(CACHE_EVENTS),
        "compiles": compiles["programs"],
    }
    compiles.clear()
    ops.reset_lowering_counts()
    device_epoch.reset_transfer_counts()

    times, traced = [], None
    if trace:
        # The first job after the warm-up runs a few percent slower on the
        # host (seen on the chip), so the traced job is the second.
        dt, answer = work.job()
        times.append(dt)
        judge(answer)
        del answer
        with tempfile.TemporaryDirectory() as tmp:
            jax.profiler.start_trace(tmp)
            dt, answer = work.job(
                lambda: jax.profiler.TraceAnnotation(tracefile.JOB_SPAN)
            )
            jax.profiler.stop_trace()
            traced = tracefile.load(tracefile.find_trace(tmp))
        times.append(dt)
        judge(answer)
        del answer
    else:
        while sum(times) < seconds:
            dt, answer = work.job()
            times.append(dt)
            judge(answer)
            del answer
    if check_chip:
        require_kernels(ops.LOWERING_COUNTS)
    device = _device_report()
    peak = device["memory_peak_bytes"]

    print(json.dumps({
        "setup": setup_counts,
        "warmup_job_s": warm_s,
        "reference_s": reference_s,
        "window": {
            "job_seconds": times,
            "compiles": compiles["programs"],
            "lowering": dict(ops.LOWERING_COUNTS),
            "transfers": dict(device_epoch.TRANSFER_COUNTS),
            "compile_cache": dict(CACHE_EVENTS),
        },
    }), flush=True)

    metrics: dict[str, dict] = {}
    result: dict = {}
    if traced is None:
        values = {
            "sorted_keys_per_s": work.n * len(times) / sum(times),
            "hbm_bytes_per_key": None if peak is None else peak / work.n,
            "setup_s": setup_s,
        }
        for m in cell.end_to_end:
            if values.get(m["name"]) is not None:
                metrics[m["name"]] = {
                    "value": values[m["name"]], "unit": m["unit"],
                }
    else:
        peaks = _read_json(BENCH / "peaks.json")
        if device["kind"] not in peaks:
            raise SystemExit(
                f"no peaks for {device['kind']!r} in bench/peaks.json"
            )
        ctx = tracefile.Context(
            traced, peaks[device["kind"]], _program_files()
        )
        for m in cell.per_layer:
            value = load_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = ctx.busy_s
        device["window_s"] = ctx.window_s
        result["breakdown"] = ctx.breakdown()

    return {
        "correct": judge.attempted > 0 and judge.failed == 0,
        "attempted": judge.attempted,
        "failed": judge.failed,
        "metrics": metrics,
        "device": device,
        **result,
        "checks": judge.checks(),
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"no repro package under {ROOT / 'src'}", file=sys.stderr)
        return 2
    cell = load_cell(args.workload)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
