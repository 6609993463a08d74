"""Dataplane benchmark matrix: topology × trace × range-mode, with artifact.

Extends ``benchmarks/run.py`` (which times the batch server on in-memory
arrays) to the packetized datapath: storage flows → switch fabric →
streaming server.  Every cell of the matrix reports

    net_<topology>_<trace>_<range_mode>,server_us,reduction=...;passes=...

where ``reduction`` compares the streaming server's time consuming the
switch-processed stream against the same server consuming the raw packet
stream (the paper's metric: the switch is in-network, its work is free to
the server), and ``range_mode`` selects how the control plane set the
segment ranges (:mod:`repro.net.control`): the paper's ``static``
equal-width, the full-data ``oracle`` quantiles, or the ``sampled``
adaptive plane that learns ranges from the live stream.  The run also
writes a schema-validated ``BENCH_net.json`` (see :mod:`benchmarks.emit`)
so the numbers accumulate as a trajectory across PRs.

The ``single``/``static`` cell is the paper's Fig. 12-14 setup and should
land within noise of ``benchmarks/run.py``'s reduction for the same
(segments, length) — printed side by side as ``batch_reduction``.

Every run also records the **hop-throughput microbench** (schema v2): one
switch hop over a ≥1M-key trace, keys/sec per hop engine — the fused
batched engine vs the pre-fusion per-segment numpy path (byte-identical
wire output, property-tested) — plus their speedup ratio, which
``benchmarks/emit.py --min-hop-speedup`` gates in CI; and the **egress
server-pool scaling sweep** (schema v3): the 1M-key trace drained by
``S ∈ {1, 2, 4}`` range-sharded streaming servers
(:class:`repro.net.egress.ServerPool`), reporting the pool makespan
(slowest server + distributed merge) per S — ``--min-server-scaling``
gates S=4 beating S=1; and the **server merge-backend sweep** (schema
v4): the same delivered 1M-key wire drained once per run-merge engine —
the eager numpy ladder vs the device-resident run-arena tournament
(byte-identical ``(output, passes)``) — with their speedup ratio, which
``--min-server-speedup`` gates in CI; and the **telemetry overhead sweep**
(schema v5): the end-to-end 1M-key pipeline run with observability off
(null tracer), with a recording :class:`repro.obs.Tracer` + metrics, and
with in-band INT columns on the wire — outputs asserted byte-identical
across modes, per-hop time/keys breakdown from the traced run's spans,
and the traced-vs-off ratio that ``--max-trace-overhead`` gates in CI;
and the **network timing sweep** (schema v6): the same 1M-key pipeline
under the per-link timing model (:mod:`repro.net.timing`) across a grid
of link bandwidths × buffer depths with 2% wire loss — per cell the
network makespan, the server makespan, sorted keys/sec through the
slower of the two, and which side bottlenecks (the compute↔network
crossover), with every cell's output asserted byte-identical to the
timeless lossless run, which ``--require-lossless-identical`` gates in
CI; and the **end-to-end device-residency sweep** (schema v7): the full
tree fabric at 10M keys with a 2-column int64 payload attached, once per
whole-epoch engine — the per-hop fused path on the Pallas backend (one
host↔device round-trip *per hop*) vs the ``device`` engine (one compiled
epoch program, keys resident from ingest to the run-arena tournament,
exactly one transfer each way) — outputs and gathered payloads asserted
byte-identical, keys/sec and records/sec per engine, and their speedup
ratio, which ``--min-e2e-speedup`` gates in CI; and the **multi-tenant
serving sweep** (schema v8): J ∈ {1, 2, 4} concurrent jobs through the
fair round-robin scheduler over one shared fabric (cross-job packing on),
reporting sustained jobs/sec, p50/p99 job latency, the minimum fair epoch
share, and per-J isolation (every tenant byte-identical to its solo run)
— ``--min-tenant-fairness`` gates the J=4 share in CI.  Every device-path
timer stops its clock only after ``jax.block_until_ready`` (async dispatch
otherwise credits device work to whoever touches the buffer next).  All
RNG (trace synthesis, interleave, control plane, wire loss) derives from
``--seed``, so an artifact reproduces across invocations.

Usage:  python benchmarks/net_bench.py [--quick] [--n N] [--scenarios]
            [--faithful-check] [--hop-n N] [--scaling-n N] [--server-n N]
            [--telemetry-n N] [--network-n N] [--e2e-n N] [--mt-n N]
            [--seed S] [--out BENCH_net.json]
"""

from __future__ import annotations

import argparse
import time

import numpy as np

try:
    import _bootstrap  # noqa: F401  (python benchmarks/net_bench.py)
except ImportError:  # pragma: no cover - python -m benchmarks.net_bench
    from benchmarks import _bootstrap  # noqa: F401

try:
    from benchmarks.emit import write_net_bench
except ImportError:  # run as a plain script: benchmarks/ is sys.path[0]
    from emit import write_net_bench

from repro.compile_cache import enable_compile_cache
from repro.core import marathon_streams, merge_sort, server_sort
from repro.core.partition import set_ranges
from repro.data import SCENARIOS, TRACES, scenario_max_value, trace_max_value
from repro.net import (
    RANGE_MODES,
    HopSpec,
    interleave_batch,
    plain_stream_sort,
    run_hop,
    run_pipeline,
    split_flows,
)

K = 10
TOPOLOGIES = [
    ("single", {}),
    ("leaf_spine", {"num_leaves": 4}),
    ("tree", {"branching": 2, "height": 3}),
]
# Scenario rows (beyond-paper workloads) added with --scenarios; kept to the
# two the control plane differentiates most to bound runtime.
BENCH_SCENARIOS = ("adversarial_skew", "drifting")

# Hop-throughput microbench geometry: one switch hop over a large trace at
# the repo's default wire payload (64 keys/packet) on a wide, 64-pipeline
# switch — the regime the fused engine exists for.  Engines are the
# byte-identical production paths ("fused") and the pre-fusion per-segment
# numpy loops ("segment"); "faithful" is element-at-a-time Python and would
# take minutes at this size.
HOP_BENCH = {"segments": 64, "length": 64, "payload": 64}
BENCH_HOP_ENGINES = ("fused", "segment")

# Egress server-pool scaling sweep (schema v3 `server_scaling`): the same
# 1M-key trace through the single switch, drained by S range-sharded
# streaming servers; the reported time is the pool *makespan* (slowest
# server + distributed merge).  CI gates S=4 beating S=1.
SCALING_SERVERS = (1, 2, 4)
SCALING_BENCH = {"segments": 16, "length": 64, "payload": 256,
                 "trace": "random", "range_mode": "oracle"}

# Server run-merge engine sweep (schema v4 `server_throughput`): the single
# streaming server draining the identical delivered 1M-key wire once per
# merge backend — the eager numpy ladder vs the device-resident run-arena
# tournament (byte-identical (output, passes), property-tested).  CI gates
# arena >= 2x the ladder.
SERVER_BACKENDS = ("numpy", "arena")
SERVER_BENCH = dict(SCALING_BENCH)

# Telemetry overhead sweep (schema v5 `telemetry`): the same end-to-end
# 1M-key pipeline run three ways — observability fully off (the null
# tracer), with a recording Tracer + metrics registry, and with INT
# per-hop metadata columns stamped onto the wire on top of that.  Outputs
# are asserted byte-identical across modes (tracing must be transparent);
# CI gates `overhead_traced_vs_off` at ``--max-trace-overhead`` (1.05).
TELEMETRY_MODES = ("off", "traced", "int")
TELEMETRY_BENCH = dict(SCALING_BENCH)

# Network timing sweep (schema v6 `network_sweep`): the same 1M-key pipeline
# run under the per-link timing model (repro.net.timing) across a grid of
# link bandwidths × output-buffer depths, with a small fixed wire-loss rate
# so the server's recovery path is always on the hook.  Each cell reports
# the network makespan (ticks → seconds via tick_ns), the server makespan,
# sorted keys/sec through the slower of the two, and which side bottlenecks
# — locating the compute↔network crossover the paper's deployment question
# asks about.  Every cell's output is compared byte-for-byte against the
# timeless lossless run; `emit.py --require-lossless-identical` gates that
# loss cost time, never keys.  rate (0, 1) means unthrottled; buffer 0
# means unbounded (JSON has no None for ints).
#   slow tail (1/16, 1/64 keys/tick) reaches past the crossover: at 10ns
#   ticks a 1M-key run needs >= 0.16s/0.64s on the wire, overtaking the
#   numpy server makespan — the grid shows bottleneck flip, not just report
#   it as absent.
NETWORK_RATES = (
    (0, 1), (8, 1), (2, 1), (1, 1), (1, 4), (1, 16), (1, 64)
)  # keys/tick
NETWORK_BUFFERS = (0, 4, 1)  # output-buffer packets
NETWORK_BENCH = dict(SCALING_BENCH, loss_rate=0.02, policy="drop")

# Fail-open degradation sweep (schema v9 `fault_tolerance`): the deepest
# stock fabric (tree, 7 hops) run under a ladder of fault plans — fault-free,
# one interior hop in pass-through, half the fabric degraded, every switch
# degraded (the paper's plain-sort baseline: the fabric forwards, the server
# sorts), plus the recovery paths (interior/leaf hop crash with reroute,
# mid-stream egress shard failover, corrupted range table falling back to
# static Alg. 2).  Every row's output is compared byte-for-byte against the
# fault-free run: faults cost throughput, never keys.  CI gates
# `--require-fault-identical` and `--min-degraded-ratio` (the
# one-hop-degraded point must keep >= 0.5x the fault-free keys/sec), and the
# sweep pins that throughput falls *toward* — never below — the
# all-pass-through floor.
FAULT_BENCH = dict(SCALING_BENCH, servers=4)
FAULT_PLANS = (
    ("fault_free", ""),
    ("one_hop_degraded", "degrade:l1n0@0"),
    ("half_degraded", "degrade:l1n0@0;degrade:l0n0@0;degrade:l0n1@0"),
    ("all_degraded", "degrade:all@0"),
    ("dead_interior", "crash:l1n0@0"),
    ("dead_leaf", "crash:l0n3@0"),
    ("shard_failover", "server_crash:1@0.5"),
    (
        "kitchen_sink",
        "crash:l1n0@0;degrade:l0n0@0;server_crash:2@0.3;corrupt_ranges@0",
    ),
)

# End-to-end device-residency sweep (schema v7 `end_to_end`): the deepest
# stock fabric (tree, 7 hops) at 10M keys with a 2-column int64 payload
# riding as packed key+row-index records, drained by the 4-server arena
# pool.  Both engines are the whole production path; the only variable is
# where the epoch lives — the fused engine re-enters Python and pays a
# host↔device round-trip at every hop, the device engine lowers the whole
# topological stage order into one jitted program with donated buffers.
# CI gates device >= 2x fused keys/sec.
E2E_ENGINES = (("fused", "pallas"), ("device", "pallas"))
E2E_BENCH = dict(
    SCALING_BENCH,
    topology="tree", branching=2, height=3,
    payload_cols=2, num_servers=4, merge_backend="arena",
)

# Multi-tenant serving sweep (schema v8 `multi_tenant`): J ∈ {1, 2, 4}
# concurrent jobs — scenario-cycled with mixed range modes, the first
# tenant always adversarial_skew under the adaptive plane — admitted into
# one shared single-switch fabric through the fair round-robin scheduler
# (:mod:`repro.net.scheduler`), a round's grants packed into shared fused
# calls.  Per J: sustained jobs/sec, p50/p99 job latency (admission wait
# included), the minimum fair epoch share across tenants, and an isolation
# check — every tenant's (output, passes) byte-identical to its solo
# ``run_pipeline`` twin.  CI gates fairness at J=4 via
# ``emit.py --min-tenant-fairness`` (which also requires all_isolated).
MT_JOBS = (1, 2, 4)
MT_SCENARIOS = ("adversarial_skew", "drifting", "sorted50", "duplicate_heavy")
MT_MODES = ("sampled", "sampled", "oracle", "static")
MT_BENCH = {"segments": 16, "length": 64, "payload": 64,
            "engine": "fused", "max_inflight": 4}


def _sync(x):
    """Block until device work behind ``x`` is done; return ``x``.

    Timer hygiene for the device paths: jax dispatches asynchronously, so a
    ``perf_counter`` delta that does not block first credits the kernel time
    to whichever later host op touches the buffer.  Numpy arrays (already
    host-resident) pass through untouched.
    """
    import jax

    if isinstance(x, np.ndarray):
        return x
    return jax.block_until_ready(x)


def end_to_end(n: int, repeats: int, seed: int = 0) -> dict:
    """Keys/sec through the whole fabric per epoch engine, payload attached.

    One warm-up run per engine pays the jit compiles (the device engine
    caches its epoch program per (graph, spec, shapes)); the timed repeats
    then measure the steady state the paper's deployment runs in.  Outputs,
    pass counts, and gathered payloads are asserted byte-identical between
    engines and against the stable-sort oracle.
    """
    cfg = dict(E2E_BENCH, n=n, repeats=repeats)
    trace = TRACES[cfg["trace"]](n, seed=seed)
    maxv = trace_max_value(cfg["trace"])
    payload = np.empty((n, cfg["payload_cols"]), dtype=np.int64)
    payload[:, 0] = trace * 7 + 3
    payload[:, 1] = np.arange(n)
    kw = dict(
        topology=cfg["topology"],
        branching=cfg["branching"],
        height=cfg["height"],
        num_segments=cfg["segments"],
        segment_length=cfg["length"],
        max_value=maxv,
        payload_size=cfg["payload"],
        num_flows=8,
        k=K,
        range_mode=cfg["range_mode"],
        num_servers=cfg["num_servers"],
        merge_backend=cfg["merge_backend"],
        payload=payload,
        seed=seed,
    )
    expected = np.sort(trace)
    order = np.argsort(trace, kind="stable")
    rows = []
    by_engine: dict[str, float] = {}
    ref_passes = None
    for engine, backend in E2E_ENGINES:
        _sync(run_pipeline(trace, engine=engine, backend=backend, **kw).output)
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            res = run_pipeline(trace, engine=engine, backend=backend, **kw)
            _sync(res.output)
            _sync(res.sorted_payload)
            times.append(time.perf_counter() - t0)
        np.testing.assert_array_equal(res.output, expected)
        np.testing.assert_array_equal(res.payload_row_order, order)
        np.testing.assert_array_equal(res.sorted_payload, payload[order])
        if ref_passes is None:
            ref_passes = res.passes
        else:
            assert res.passes == ref_passes, "epoch engines disagree on passes"
        secs = float(np.min(times))
        by_engine[engine] = secs
        rows.append(
            {
                "engine": engine,
                "backend": backend,
                "seconds": secs,
                "keys_per_sec": n / secs,
                "records_per_sec": n / secs,
                "payload_cols": int(cfg["payload_cols"]),
            }
        )
    return {
        "config": cfg,
        "rows": rows,
        "speedup_device_vs_fused": by_engine["fused"] / by_engine["device"],
    }


def hop_throughput(n: int, repeats: int, seed: int = 0) -> dict:
    """Keys/sec through one switch hop, per engine, on the random trace."""
    cfg = dict(HOP_BENCH, n=n, trace="random", repeats=repeats)
    trace = TRACES["random"](n, seed=seed)
    maxv = trace_max_value("random")
    batch = interleave_batch(
        split_flows(trace, 8, cfg["payload"]), "round_robin"
    )
    spec = HopSpec(
        cfg["segments"],
        cfg["length"],
        maxv,
        set_ranges(maxv, cfg["segments"]),
        payload_size=cfg["payload"],
    )
    rows = []
    by_engine = {}
    for engine in BENCH_HOP_ENGINES:
        times = []
        for _ in range(repeats):
            t0 = time.perf_counter()
            out, _ = run_hop(batch, spec, "hop", engine)
            times.append(time.perf_counter() - t0)
        np.testing.assert_array_equal(
            np.sort(out.values), np.sort(trace)
        )
        secs = float(np.min(times))
        by_engine[engine] = secs
        rows.append(
            {"engine": engine, "seconds": secs, "keys_per_sec": n / secs}
        )
    return {
        "config": cfg,
        "rows": rows,
        "speedup_fused_vs_segment": by_engine["segment"] / by_engine["fused"],
    }


def server_scaling(n: int, repeats: int, seed: int = 0) -> dict:
    """Pool makespan at S ∈ {1, 2, 4} egress servers on the 1M-key trace.

    Every run is verified byte-identical to ``np.sort`` (and therefore to
    every other S — int64 keys have no identity beyond their value), so the
    sweep measures exactly the scale-out claim: each server sorts only its
    contiguous range shard, the distributed merge concatenates.
    """
    cfg = dict(SCALING_BENCH, n=n, repeats=repeats)
    trace = TRACES[cfg["trace"]](n, seed=seed)
    maxv = trace_max_value(cfg["trace"])
    expected = np.sort(trace)
    rows = []
    by_s: dict[int, float] = {}
    for S in SCALING_SERVERS:
        # (makespan, merge) are kept per repeat so the emitted row's fields
        # all describe the same (fastest) run; imbalance is deterministic.
        samples = []
        for _ in range(repeats):
            res = run_pipeline(
                trace,
                topology="single",
                num_segments=cfg["segments"],
                segment_length=cfg["length"],
                max_value=maxv,
                payload_size=cfg["payload"],
                num_flows=8,
                k=K,
                range_mode=cfg["range_mode"],
                num_servers=S,
                seed=seed,
            )
            samples.append(
                (float(res.server_seconds), float(res.pool_merge_seconds))
            )
        np.testing.assert_array_equal(res.output, expected)
        secs, merge = min(samples)
        by_s[S] = secs
        rows.append(
            {
                "num_servers": S,
                "server_seconds": secs,
                "merge_seconds": merge,
                "server_imbalance": float(res.server_imbalance),
            }
        )
    return {
        "config": cfg,
        "rows": rows,
        "speedup_s4_vs_s1": by_s[1] / by_s[4],
    }


def server_throughput(n: int, repeats: int, seed: int = 0) -> dict:
    """Ingest+finish seconds per merge backend on the same delivered wire.

    The fabric runs once; each backend then drains the identical delivered
    batch through a fresh :class:`~repro.net.server.StreamingServer`, so the
    comparison isolates exactly the run-merge engine (reorder buffer and run
    detection are shared code).  Outputs and pass counts are asserted
    byte-identical across backends and against ``np.sort``.
    """
    from repro.net.server import StreamingServer

    cfg = dict(SERVER_BENCH, n=n, repeats=repeats)
    trace = TRACES[cfg["trace"]](n, seed=seed)
    maxv = trace_max_value(cfg["trace"])
    delivered = run_pipeline(
        trace,
        topology="single",
        num_segments=cfg["segments"],
        segment_length=cfg["length"],
        max_value=maxv,
        payload_size=cfg["payload"],
        num_flows=8,
        k=K,
        range_mode=cfg["range_mode"],
        seed=seed,
    ).delivered
    expected = np.sort(trace)
    rows = []
    by_backend: dict[str, float] = {}
    ref = None
    for backend in SERVER_BACKENDS:
        times = []
        for _ in range(repeats):
            server = StreamingServer(
                cfg["segments"], k=K, merge_backend=backend
            )
            t0 = time.perf_counter()
            server.ingest_batch(delivered)
            out, passes = server.finish()
            out = _sync(out)  # arena backend: device-resident tournament
            times.append(time.perf_counter() - t0)
        np.testing.assert_array_equal(out, expected)
        if ref is None:
            ref = passes
        else:
            assert passes == ref, "merge backends disagree on pass counts"
        secs = float(np.min(times))
        by_backend[backend] = secs
        rows.append(
            {
                "merge_backend": backend,
                "server_seconds": secs,
                "keys_per_sec": n / secs,
            }
        )
    return {
        "config": cfg,
        "rows": rows,
        "speedup_arena_vs_numpy": by_backend["numpy"] / by_backend["arena"],
    }


def telemetry_overhead(n: int, repeats: int, seed: int = 0) -> dict:
    """End-to-end pipeline seconds per observability mode, plus per-hop cost.

    Three modes on the identical trace and config: ``off`` (null tracer —
    the production path), ``traced`` (recording :class:`repro.obs.Tracer` +
    metrics registry), and ``int`` (traced plus in-band per-hop metadata
    columns on the wire).  Outputs are asserted byte-identical across all
    three — observability must be transparent — and the traced run's hop
    spans become the per-hop time/keys breakdown the report renders.
    """
    from repro.obs import MetricsRegistry, Tracer

    cfg = dict(TELEMETRY_BENCH, n=n, repeats=repeats)
    trace = TRACES[cfg["trace"]](n, seed=seed)
    maxv = trace_max_value(cfg["trace"])
    expected = np.sort(trace)
    kw = dict(
        topology="single",
        num_segments=cfg["segments"],
        segment_length=cfg["length"],
        max_value=maxv,
        payload_size=cfg["payload"],
        num_flows=8,
        k=K,
        range_mode=cfg["range_mode"],
        seed=seed,
    )
    # Interleave the modes round-robin (off, traced, int, off, traced, …)
    # rather than timing each mode's repeats in a block: allocator and page
    # cache state drift over a block schedule and masquerade as tracer
    # overhead.  Min-per-mode over interleaved rounds isolates the real cost.
    run_pipeline(trace, **kw)  # warm-up (imports, allocator growth)
    times: dict[str, list[float]] = {mode: [] for mode in TELEMETRY_MODES}
    best_tracer = None
    for _ in range(repeats):
        for mode in TELEMETRY_MODES:
            tracer = Tracer() if mode != "off" else None
            metrics = MetricsRegistry() if mode != "off" else None
            t0 = time.perf_counter()
            res = run_pipeline(
                trace, tracer=tracer, metrics=metrics,
                int_telemetry=mode == "int", **kw
            )
            dt = time.perf_counter() - t0
            if mode == "traced" and dt <= min(times[mode], default=np.inf):
                best_tracer = tracer
            times[mode].append(dt)
            np.testing.assert_array_equal(res.output, expected)
    rows = []
    by_mode: dict[str, float] = {}
    per_hop: list[dict] = []
    for mode in TELEMETRY_MODES:
        secs = float(np.min(times[mode]))
        by_mode[mode] = secs
        rows.append(
            {"mode": mode, "pipeline_seconds": secs, "keys_per_sec": n / secs}
        )
    for sp in best_tracer.find(cat="hop"):
        per_hop.append(
            {
                "hop": sp.name.removeprefix("hop:"),
                "seconds": float(sp.seconds),
                "keys_in": int(sp.args.get("keys", 0)),
                "keys_out": int(sp.args.get("keys_out", 0)),
            }
        )
    return {
        "config": cfg,
        "rows": rows,
        "per_hop": per_hop,
        "overhead_traced_vs_off": by_mode["traced"] / by_mode["off"],
        "overhead_int_vs_off": by_mode["int"] / by_mode["off"],
    }


def network_sweep(n: int, repeats: int, seed: int = 0) -> dict:
    """Keys/sec and bottleneck per (link rate × buffer depth) grid cell.

    One lossless timeless reference run anchors byte-identity; every timed
    cell then runs the full pipeline under a :class:`repro.net.NetworkConfig`
    with 2% wire loss (drop policy — NACK + replay; the raw egress wire's
    duplicates and late retransmits exercise the server's recovery mode).
    ``keys_per_sec`` charges the slower of the network and server makespans
    — the crossover row is where ``bottleneck`` flips from compute to
    network as the link slows or the buffer shrinks.
    """
    from repro.net import LinkSpec, NetworkConfig

    cfg = dict(NETWORK_BENCH, n=n, repeats=repeats)
    trace = TRACES[cfg["trace"]](n, seed=seed)
    maxv = trace_max_value(cfg["trace"])
    kw = dict(
        topology="single",
        num_segments=cfg["segments"],
        segment_length=cfg["length"],
        max_value=maxv,
        payload_size=cfg["payload"],
        num_flows=8,
        k=K,
        range_mode=cfg["range_mode"],
        seed=seed,
    )
    ref = run_pipeline(trace, **kw)
    np.testing.assert_array_equal(ref.output, np.sort(trace))
    rows = []
    crossover = 0.0  # slowest-to-fastest rate at which the network binds
    for numer, denom in NETWORK_RATES:
        for buf in NETWORK_BUFFERS:
            net = NetworkConfig(
                link=LinkSpec(
                    latency=2,
                    rate_numer=numer or None,
                    rate_denom=denom,
                    buffer_packets=buf or None,
                    policy=cfg["policy"],
                    loss_rate=cfg["loss_rate"],
                ),
                switch_latency=1,
                seed=seed,
            )
            samples = []
            for _ in range(repeats):
                res = run_pipeline(trace, network=net, **kw)
                samples.append(float(res.server_seconds))
            server_s = float(np.min(samples))
            report = res.network
            net_s = float(report.seconds)
            identical = bool(np.array_equal(res.output, ref.output))
            bottleneck = "network" if net_s >= server_s else "compute"
            if bottleneck == "network" and buf == 0 and numer:
                crossover = max(crossover, numer / denom)
            rows.append(
                {
                    "rate_numer": int(numer),
                    "rate_denom": int(denom),
                    "buffer_packets": int(buf),
                    "makespan_ticks": int(report.makespan_ticks),
                    "network_seconds": net_s,
                    "server_seconds": server_s,
                    "keys_per_sec": n / max(net_s, server_s),
                    "bottleneck": bottleneck,
                    "drops": int(report.drops),
                    "retransmits": int(report.retransmits),
                    "lossless_identical": identical,
                }
            )
    return {
        "config": cfg,
        "rows": rows,
        "all_lossless_identical": all(r["lossless_identical"] for r in rows),
        "crossover_keys_per_tick": crossover,
    }


def multi_tenant(n: int, repeats: int, seed: int = 0) -> dict:
    """Jobs/sec, latency percentiles, fairness, and isolation per J.

    Each repeat rebuilds the job set (the scheduler consumes per-job
    control-plane state); the fastest wall-clock repeat's figures are
    reported.  The isolation column then re-runs every tenant solo through
    ``run_pipeline`` with identical fabric parameters and compares
    ``(output, passes)`` byte-for-byte — concurrency and cross-job packing
    must never change a tenant's bytes.
    """
    from repro.net import Job, run_job_solo, run_jobs

    cfg = dict(MT_BENCH, n=n, repeats=repeats)
    fabric = dict(
        topology="single",
        num_segments=cfg["segments"],
        segment_length=cfg["length"],
        payload_size=cfg["payload"],
        engine=cfg["engine"],
        max_inflight=cfg["max_inflight"],
    )

    def make_jobs(J: int) -> list:
        jobs = []
        for t in range(J):
            name = MT_SCENARIOS[t % len(MT_SCENARIOS)]
            jobs.append(
                Job(
                    t,
                    SCENARIOS[name](n, seed=seed + t),
                    seed=seed + t,
                    range_mode=MT_MODES[t % len(MT_MODES)],
                    max_value=scenario_max_value(name),
                )
            )
        return jobs

    rows = []
    fairness_at_j4 = 0.0
    for J in MT_JOBS:
        best = None
        for _ in range(repeats):
            res = run_jobs(make_jobs(J), **fabric)
            if best is None or res.elapsed_seconds < best.elapsed_seconds:
                best = res
        isolated = True
        for job in make_jobs(J):
            solo = run_job_solo(job, **fabric)
            jr = best.by_tenant(job.tenant_id)
            isolated &= bool(
                np.array_equal(jr.output, solo.output)
                and jr.passes == solo.passes
            )
        rows.append(
            {
                "num_jobs": J,
                "elapsed_seconds": float(best.elapsed_seconds),
                "jobs_per_sec": float(best.jobs_per_sec),
                "p50_latency_s": float(best.p50_latency_s),
                "p99_latency_s": float(best.p99_latency_s),
                "fairness": float(best.fairness),
                "rounds": int(best.rounds),
                "fabric_calls": int(best.fabric_calls),
                "packed_calls": int(best.packed_calls),
                "isolation_ok": isolated,
            }
        )
        if J == 4:
            fairness_at_j4 = float(best.fairness)
    return {
        "config": cfg,
        "rows": rows,
        "fairness_at_j4": fairness_at_j4,
        "all_isolated": all(r["isolation_ok"] for r in rows),
    }


def fault_tolerance(n: int, repeats: int, seed: int = 0) -> dict:
    """Keys/sec and byte-identity per fault plan on the deep tree fabric.

    The fault-free row anchors both the reference output and the reference
    throughput; every faulted row must reproduce the bytes exactly and is
    reported as a throughput ratio against that anchor.  The all-degraded
    row is the floor — the fabric contributes nothing and the server does
    every merge, i.e. the paper's plain-sort baseline running over the same
    wire — and graceful degradation means every partial-fault ratio sits
    between the floor and 1.0 (modulo timer noise; the CI gate holds the
    single-hop point at >= 0.5x).
    """
    cfg = dict(FAULT_BENCH, n=n, repeats=repeats)
    trace = TRACES[cfg["trace"]](n, seed=seed)
    maxv = trace_max_value(cfg["trace"])
    kw = dict(
        topology="tree",
        branching=2,
        height=3,
        num_segments=cfg["segments"],
        segment_length=cfg["length"],
        max_value=maxv,
        payload_size=cfg["payload"],
        num_flows=8,
        k=K,
        range_mode=cfg["range_mode"],
        num_servers=cfg["servers"],
        seed=seed,
    )
    rows = []
    ref_output = None
    ref_kps = 0.0
    for name, spec in FAULT_PLANS:
        t, res = _best(
            lambda: run_pipeline(trace, fault_plan=spec or None, **kw),
            repeats,
        )
        kps = n / t
        if name == "fault_free":
            ref_output = res.output
            ref_kps = kps
            np.testing.assert_array_equal(ref_output, np.sort(trace))
        identical = bool(np.array_equal(res.output, ref_output))
        rows.append(
            {
                "plan": name,
                "spec": spec,
                "seconds": float(t),
                "keys_per_sec": float(kps),
                "throughput_ratio": float(kps / ref_kps),
                "identical": identical,
                "hops_dead": int(res.fault_hops_dead),
                "hops_degraded": int(res.fault_hops_degraded),
                "servers_failed_over": int(res.servers_failed_over),
                "range_fallbacks": int(res.range_fallbacks),
            }
        )
    by_plan = {r["plan"]: r for r in rows}
    return {
        "config": cfg,
        "rows": rows,
        "all_faults_identical": all(r["identical"] for r in rows),
        "degraded_ratio_single_hop": by_plan["one_hop_degraded"][
            "throughput_ratio"
        ],
        "floor_ratio": by_plan["all_degraded"]["throughput_ratio"],
    }


def _best(fn, repeats: int):
    """Min-time over repeats (noise-robust) + the last result."""
    times, out = [], None
    for _ in range(repeats):
        t0 = time.perf_counter()
        out = fn()
        times.append(time.perf_counter() - t0)
    return float(np.min(times)), out


def batch_reduction(trace, maxv, segs, length, repeats) -> float:
    """run.py's metric for the same geometry: batch server, no packets."""
    t_base, (out, _) = _best(lambda: merge_sort(trace, k=K), repeats)
    np.testing.assert_array_equal(out, np.sort(trace))
    streams, _ = marathon_streams(trace, segs, length, maxv)
    t_mm, (out, _) = _best(lambda: server_sort(streams, k=K), repeats)
    np.testing.assert_array_equal(out, np.sort(trace))
    return 1 - t_mm / t_base


def _weighted(stats, attr: str) -> float:
    total = sum(st.arrivals for st in stats) or 1
    return sum(getattr(st, attr) * st.arrivals for st in stats) / total


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--n", type=int, default=400_000)
    ap.add_argument("--repeats", type=int, default=2)
    ap.add_argument("--segments", type=int, default=16)
    ap.add_argument("--length", type=int, default=64)
    ap.add_argument("--payload", type=int, default=256)
    ap.add_argument("--quick", action="store_true", help="100k values, 2 repeats")
    ap.add_argument(
        "--scenarios", action="store_true",
        help=f"also bench the scenario workloads {BENCH_SCENARIOS}",
    )
    ap.add_argument(
        "--out", default="BENCH_net.json",
        help="artifact path ('' disables the artifact)",
    )
    ap.add_argument(
        "--faithful-check",
        action="store_true",
        help="also run the element-at-a-time switch on a small slice",
    )
    ap.add_argument(
        "--hop-n", type=int, default=1_000_000,
        help="trace size for the per-engine hop-throughput microbench "
        "(>= 1M keys; not reduced by --quick)",
    )
    ap.add_argument(
        "--hop-repeats", type=int, default=5,
        help="repeats for the hop-throughput microbench (min-time wins)",
    )
    ap.add_argument(
        "--scaling-n", type=int, default=1_000_000,
        help="trace size for the egress server-pool scaling sweep "
        "(>= 1M keys; not reduced by --quick)",
    )
    ap.add_argument(
        "--scaling-repeats", type=int, default=2,
        help="repeats for the server-pool scaling sweep (min-time wins)",
    )
    ap.add_argument(
        "--server-n", type=int, default=1_000_000,
        help="trace size for the per-backend server-throughput sweep "
        "(>= 1M keys; not reduced by --quick)",
    )
    ap.add_argument(
        "--server-repeats", type=int, default=3,
        help="repeats for the server-throughput sweep (min-time wins; the "
        "first arena repeat pays the jit compiles, so >= 2 to measure warm)",
    )
    ap.add_argument(
        "--telemetry-n", type=int, default=1_000_000,
        help="trace size for the telemetry-overhead sweep (>= 1M keys; "
        "not reduced by --quick — the overhead gate needs real work to "
        "amortize against)",
    )
    ap.add_argument(
        "--telemetry-repeats", type=int, default=3,
        help="repeats for the telemetry-overhead sweep (min-time wins)",
    )
    ap.add_argument(
        "--network-n", type=int, default=1_000_000,
        help="trace size for the network timing sweep (>= 1M keys; not "
        "reduced by --quick — the crossover needs the real server makespan)",
    )
    ap.add_argument(
        "--network-repeats", type=int, default=2,
        help="repeats for the network timing sweep (min server time wins; "
        "the tick-counted network makespan is deterministic)",
    )
    ap.add_argument(
        "--e2e-n", type=int, default=10_000_000,
        help="trace size for the end-to-end device-residency sweep (the "
        "ISSUE gate cell is 10M keys with payload attached; not reduced "
        "by --quick — per-hop dispatch overhead only shows at scale)",
    )
    ap.add_argument(
        "--e2e-repeats", type=int, default=1,
        help="timed repeats for the end-to-end sweep (min-time wins; a "
        "separate warm-up run per engine pays the jit compiles first, so "
        "one warm repeat suffices — the per-hop fused run is ~7 minutes "
        "at 10M keys; raise for tighter timings)",
    )
    ap.add_argument(
        "--fault-n", type=int, default=1_000_000,
        help="trace size for the fail-open degradation sweep (>= 1M keys; "
        "not reduced by --quick — the degraded-throughput ratio gate needs "
        "fabric work that dwarfs dispatch overhead)",
    )
    ap.add_argument(
        "--fault-repeats", type=int, default=2,
        help="repeats for the fail-open degradation sweep (min-time wins)",
    )
    ap.add_argument(
        "--mt-n", type=int, default=200_000,
        help="keys per job for the multi-tenant serving sweep (per tenant; "
        "not reduced by --quick — the fairness/isolation gate needs "
        "multi-epoch adaptive jobs)",
    )
    ap.add_argument(
        "--mt-repeats", type=int, default=2,
        help="repeats for the multi-tenant sweep (fastest wall-clock wins)",
    )
    ap.add_argument(
        "--seed", type=int, default=0,
        help="base RNG seed: trace synthesis (offset per workload), flow "
        "interleave, and control-plane sampling all derive from it, so a "
        "BENCH_net.json is reproducible across invocations",
    )
    args = ap.parse_args()
    enable_compile_cache()
    n, repeats = (100_000, 2) if args.quick else (args.n, args.repeats)
    segs, length = args.segments, args.length

    def emit(name: str, us: float, derived: str) -> None:
        print(f"{name},{us:.1f},{derived}", flush=True)

    print(
        f"# net_bench n={n} repeats={repeats} segments={segs} "
        f"length={length} payload={args.payload} k={K}",
        flush=True,
    )
    # Seed every generator explicitly (offset per workload so traces stay
    # decorrelated): a rerun with the same --seed reproduces the artifact.
    workloads: list[tuple[str, np.ndarray, int]] = [
        (name, gen(n, seed=args.seed + i), trace_max_value(name))
        for i, (name, gen) in enumerate(TRACES.items())
    ]
    if args.scenarios:
        workloads += [
            (name, SCENARIOS[name](n, seed=args.seed + 100 + i),
             scenario_max_value(name))
            for i, name in enumerate(BENCH_SCENARIOS)
        ]

    rows: list[dict] = []
    for trace_name, trace, maxv in workloads:
        # Baseline: server-only seconds (excludes packetization — the paper's
        # metric charges the server, not the network).
        plain_times = []
        for _ in range(repeats):
            out, plain_passes, secs = plain_stream_sort(trace, args.payload, k=K)
            plain_times.append(secs)
        t_plain = float(np.min(plain_times))
        np.testing.assert_array_equal(out, np.sort(trace))
        emit(
            f"net_plain_{trace_name}",
            t_plain * 1e6,
            f"passes={plain_passes[0]}",
        )

        batch_red = batch_reduction(trace, maxv, segs, length, repeats)

        for topo, topo_kw in TOPOLOGIES:
            for mode in RANGE_MODES:
                server_times = []
                for _ in range(repeats):
                    res = run_pipeline(
                        trace,
                        topology=topo,
                        num_segments=segs,
                        segment_length=length,
                        max_value=maxv,
                        payload_size=args.payload,
                        num_flows=8,
                        k=K,
                        range_mode=mode,
                        seed=args.seed,
                        **topo_kw,
                    )
                    server_times.append(res.server_seconds)
                t_server = float(np.min(server_times))
                np.testing.assert_array_equal(res.output, np.sort(trace))
                red = 1 - t_server / t_plain
                passes = int(max(res.passes))
                pass_red = (
                    1 - passes / plain_passes[0] if plain_passes[0] else 0.0
                )
                derived = (
                    f"reduction={red:.3f};passes={passes};"
                    f"hops={len(res.hop_stats)};epochs={res.num_epochs};"
                    f"imbalance={_weighted(res.hop_stats, 'load_imbalance'):.2f}"
                )
                if topo == "single" and mode == "static":
                    derived += f";batch_reduction={batch_red:.3f}"
                emit(f"net_{topo}_{trace_name}_{mode}", t_server * 1e6, derived)
                rows.append(
                    {
                        "topology": topo,
                        "trace": trace_name,
                        "range_mode": mode,
                        "plain_seconds": t_plain,
                        "server_seconds": t_server,
                        "reduction": red,
                        "passes": passes,
                        "plain_passes": int(plain_passes[0]),
                        "pass_reduction": pass_red,
                        "hops": len(res.hop_stats),
                        "epochs": int(res.num_epochs),
                        "load_imbalance": _weighted(
                            res.hop_stats, "load_imbalance"
                        ),
                        "mean_run_len": _weighted(
                            res.hop_stats, "mean_run_len"
                        ),
                    }
                )

        if args.faithful_check:
            small = trace[:4000]
            rf = run_pipeline(
                small, topology="single", faithful=True,
                num_segments=segs, segment_length=length, max_value=maxv,
                payload_size=args.payload, verify=True,
            )
            emit(
                f"net_faithful_{trace_name}", 0.0,
                f"ok_n={small.size};passes={max(rf.passes)}",
            )

    hop = hop_throughput(args.hop_n, args.hop_repeats, seed=args.seed)
    for r in hop["rows"]:
        emit(
            f"hop_{r['engine']}_random",
            r["seconds"] * 1e6,
            f"keys_per_sec={r['keys_per_sec']:.0f};n={hop['config']['n']}",
        )
    print(
        f"# hop speedup fused vs segment: "
        f"{hop['speedup_fused_vs_segment']:.2f}x",
        flush=True,
    )

    scaling = server_scaling(
        args.scaling_n, args.scaling_repeats, seed=args.seed
    )
    for r in scaling["rows"]:
        emit(
            f"pool_scaling_s{r['num_servers']}_{scaling['config']['trace']}",
            r["server_seconds"] * 1e6,
            f"merge_us={r['merge_seconds'] * 1e6:.1f};"
            f"imbalance={r['server_imbalance']:.2f};"
            f"n={scaling['config']['n']}",
        )
    print(
        f"# pool makespan speedup S=4 vs S=1: "
        f"{scaling['speedup_s4_vs_s1']:.2f}x",
        flush=True,
    )

    server = server_throughput(
        args.server_n, args.server_repeats, seed=args.seed
    )
    for r in server["rows"]:
        emit(
            f"server_{r['merge_backend']}_{server['config']['trace']}",
            r["server_seconds"] * 1e6,
            f"keys_per_sec={r['keys_per_sec']:.0f};"
            f"n={server['config']['n']}",
        )
    print(
        f"# server merge speedup arena vs numpy: "
        f"{server['speedup_arena_vs_numpy']:.2f}x",
        flush=True,
    )

    telemetry = telemetry_overhead(
        args.telemetry_n, args.telemetry_repeats, seed=args.seed
    )
    for r in telemetry["rows"]:
        emit(
            f"telemetry_{r['mode']}_{telemetry['config']['trace']}",
            r["pipeline_seconds"] * 1e6,
            f"keys_per_sec={r['keys_per_sec']:.0f};"
            f"n={telemetry['config']['n']}",
        )
    print(
        f"# telemetry overhead traced vs off: "
        f"{telemetry['overhead_traced_vs_off']:.3f}x "
        f"(int: {telemetry['overhead_int_vs_off']:.3f}x)",
        flush=True,
    )

    network = network_sweep(
        args.network_n, args.network_repeats, seed=args.seed
    )
    for r in network["rows"]:
        rate = (
            "inf" if not r["rate_numer"]
            else f"{r['rate_numer']}/{r['rate_denom']}"
        )
        buf = r["buffer_packets"] or "inf"
        emit(
            f"network_rate{rate.replace('/', 'd')}_buf{buf}",
            r["network_seconds"] * 1e6,
            f"keys_per_sec={r['keys_per_sec']:.0f};"
            f"bottleneck={r['bottleneck']};drops={r['drops']};"
            f"identical={int(r['lossless_identical'])}",
        )
    print(
        f"# network sweep: lossless-identical on all "
        f"{len(network['rows'])} cells: {network['all_lossless_identical']}; "
        f"network binds at <= {network['crossover_keys_per_tick']:.2f} "
        f"keys/tick (unbounded buffer)",
        flush=True,
    )

    faults = fault_tolerance(
        args.fault_n, args.fault_repeats, seed=args.seed
    )
    for r in faults["rows"]:
        emit(
            f"fault_{r['plan']}",
            r["seconds"] * 1e6,
            f"keys_per_sec={r['keys_per_sec']:.0f};"
            f"ratio={r['throughput_ratio']:.2f};"
            f"identical={int(r['identical'])};"
            f"dead={r['hops_dead']};degraded={r['hops_degraded']};"
            f"failovers={r['servers_failed_over']}",
        )
    print(
        f"# fail-open: byte-identical under all "
        f"{len(faults['rows'])} fault plans: "
        f"{faults['all_faults_identical']}; one hop degraded keeps "
        f"{faults['degraded_ratio_single_hop']:.2f}x throughput "
        f"(all-pass-through floor: {faults['floor_ratio']:.2f}x)",
        flush=True,
    )

    mt = multi_tenant(args.mt_n, args.mt_repeats, seed=args.seed)
    for r in mt["rows"]:
        emit(
            f"mt_j{r['num_jobs']}_{mt['config']['engine']}",
            r["elapsed_seconds"] * 1e6,
            f"jobs_per_sec={r['jobs_per_sec']:.2f};"
            f"p50_s={r['p50_latency_s']:.3f};"
            f"p99_s={r['p99_latency_s']:.3f};"
            f"fairness={r['fairness']:.2f};"
            f"packed={r['packed_calls']}/{r['fabric_calls']};"
            f"isolated={int(r['isolation_ok'])}",
        )
    print(
        f"# multi-tenant: fairness at J=4: {mt['fairness_at_j4']:.2f}; "
        f"all tenants byte-identical to solo: {mt['all_isolated']}",
        flush=True,
    )

    e2e = end_to_end(args.e2e_n, args.e2e_repeats, seed=args.seed)
    for r in e2e["rows"]:
        emit(
            f"e2e_{r['engine']}_{e2e['config']['topology']}",
            r["seconds"] * 1e6,
            f"keys_per_sec={r['keys_per_sec']:.0f};"
            f"records_per_sec={r['records_per_sec']:.0f};"
            f"payload_cols={r['payload_cols']};n={e2e['config']['n']}",
        )
    print(
        f"# end-to-end speedup device vs fused (per-hop): "
        f"{e2e['speedup_device_vs_fused']:.2f}x",
        flush=True,
    )

    if args.out:
        config = {
            "n": n,
            "repeats": repeats,
            "segments": segs,
            "length": length,
            "payload": args.payload,
            "k": K,
            "quick": bool(args.quick),
            "seed": int(args.seed),
        }
        write_net_bench(
            args.out, config, rows, hop_throughput=hop,
            server_scaling=scaling, server_throughput=server,
            telemetry=telemetry, network_sweep=network, end_to_end=e2e,
            multi_tenant=mt, fault_tolerance=faults,
        )
        print(f"# wrote {args.out} ({len(rows)} rows)", flush=True)


if __name__ == "__main__":
    main()
