"""Paper-reproduction benchmark harness — one function per paper table/figure.

Emits ``name,us_per_call,derived`` CSV lines.  The server implementation is
numpy (the paper's is C), so absolute times differ; the paper's own metric —
*relative* runtime reduction of MergeMarathon vs plain merge sort on the
identical server — is what each figure reproduces.

    bench_baseline  — Fig. 11: plain merge sort per trace (avg + median)
    bench_sweep     — Fig. 12-14: segments × stages grid per trace
    bench_cuts      — Fig. 16-18: 2D cuts derived from the sweep
    bench_runstats  — §6.3: run statistics + unique values per trace
    bench_theory    — §3.2: measured merge passes == ceil-log_k(N/(S·r̃))
"""

from __future__ import annotations

import argparse
import time

import numpy as np

try:
    import _bootstrap  # noqa: F401  (python benchmarks/run.py)
except ImportError:  # pragma: no cover - python -m benchmarks.run
    from benchmarks import _bootstrap  # noqa: F401

from repro.compile_cache import enable_compile_cache
from repro.core import (
    RunStats,
    marathon_streams,
    merge_passes,
    merge_sort,
    run_starts,
    server_sort,
)
from repro.data import TRACES, trace_max_value

SEGMENTS = [1, 4, 8, 16, 32, 64, 128]
LENGTHS = [4, 8, 16, 32, 64, 128]
K = 10  # paper: merge sort order k = 10 everywhere


def _time(fn, repeats: int):
    times = []
    out = None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        times.append(time.perf_counter() - t0)
    return float(np.mean(times)), float(np.median(times)), out


def bench_baseline(n: int, repeats: int, emit) -> dict:
    base = {}
    for name, gen in TRACES.items():
        trace = gen(n)
        avg, med, (out, passes) = _time(lambda: merge_sort(trace, k=K), repeats)
        np.testing.assert_array_equal(out, np.sort(trace))
        base[name] = avg
        emit(
            f"fig11_baseline_{name}",
            avg * 1e6,
            f"median_s={med:.3f};passes={passes}",
        )
    return base


def bench_sweep(n: int, repeats: int, base: dict, emit) -> dict:
    results = {}
    for name, gen in TRACES.items():
        trace = gen(n)
        maxv = trace_max_value(name)
        for segs in SEGMENTS:
            for length in LENGTHS:
                streams, _ = marathon_streams(trace, segs, length, maxv)
                avg, med, (out, _) = _time(
                    lambda: server_sort(streams, k=K), repeats
                )
                np.testing.assert_array_equal(out, np.sort(trace))
                red = 1 - avg / base[name]
                results[(name, segs, length)] = (avg, med, red)
                emit(
                    f"fig12-14_sweep_{name}_s{segs}_y{length}",
                    avg * 1e6,
                    f"median_s={med:.3f};reduction={red:.3f}",
                )
    return results


def bench_cuts(results: dict, emit) -> None:
    """Fig. 16-18 cuts: fixed length vs segments and vice versa."""
    for name in TRACES:
        for length in LENGTHS:
            row = [results[(name, s, length)][0] for s in SEGMENTS]
            emit(
                f"fig16-18_cut_{name}_fixed_y{length}",
                float(np.mean(row)) * 1e6,
                "avg_s_per_segments=" + "/".join(f"{v:.3f}" for v in row),
            )
        for segs in SEGMENTS:
            row = [results[(name, segs, ln)][0] for ln in LENGTHS]
            emit(
                f"fig16-18_cut_{name}_fixed_s{segs}",
                float(np.mean(row)) * 1e6,
                "avg_s_per_length=" + "/".join(f"{v:.3f}" for v in row),
            )


def bench_runstats(n: int, emit) -> None:
    for name, gen in TRACES.items():
        trace = gen(n)
        uniq = int(np.unique(trace).size)
        maxv = trace_max_value(name)
        s0 = RunStats.of(trace)
        emit(
            f"runstats_{name}_raw",
            0.0,
            f"uniques={uniq};runs={s0.num_runs};mean_len={s0.mean_len:.2f}",
        )
        for segs, length in [(1, 32), (16, 16), (16, 128)]:
            streams, _ = marathon_streams(trace, segs, length, maxv)
            stats = [RunStats.of(s) for s in streams if s.size]
            runs = int(np.sum([s.num_runs for s in stats]))
            mean_len = float(np.mean([s.mean_len for s in stats]))
            emit(
                f"runstats_{name}_s{segs}_y{length}",
                0.0,
                f"runs={runs};mean_len={mean_len:.2f}",
            )


def bench_theory(n: int, emit) -> None:
    """Measured pass counts == ceil-log_k of the initial run count (§3.2)."""
    for name, gen in TRACES.items():
        trace = gen(n)
        maxv = trace_max_value(name)
        for segs, length in [(1, 1), (4, 16), (16, 64)]:
            streams, _ = marathon_streams(trace, segs, length, maxv)
            worst = 0
            for s in streams:
                if not s.size:
                    continue
                _, passes = merge_sort(s, k=K)
                pred = merge_passes(run_starts(s).size, K)
                assert passes == pred, (name, segs, length, passes, pred)
                worst = max(worst, passes)
            emit(f"theory_{name}_s{segs}_y{length}", 0.0, f"max_passes={worst}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=1_000_000,
                    help="trace length (paper: 100M/77M; scaled for 1 core)")
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--quick", action="store_true",
                    help="400k values, sweep subset")
    args = ap.parse_args()
    enable_compile_cache()
    n, repeats = (400_000, 2) if args.quick else (args.n, args.repeats)
    if args.quick:
        global SEGMENTS, LENGTHS
        SEGMENTS = [1, 8, 16, 64]
        LENGTHS = [4, 16, 64]

    def emit(name: str, us: float, derived: str) -> None:
        print(f"{name},{us:.1f},{derived}", flush=True)

    print(f"# traces n={n} repeats={repeats} k={K}", flush=True)
    base = bench_baseline(n, repeats, emit)
    results = bench_sweep(n, repeats, base, emit)
    bench_cuts(results, emit)
    bench_runstats(n, emit)
    bench_theory(min(n, 200_000), emit)

    # headline: the paper reports 20-75% reduction, avg ~50%
    reds = [r[2] for r in results.values()]
    emit(
        "headline_reduction",
        0.0,
        f"min={min(reds):.3f};max={max(reds):.3f};mean={float(np.mean(reds)):.3f}",
    )


if __name__ == "__main__":
    main()
