"""End-to-end datapath harness: generators → flows → topology → server.

This is the paper's Fig. 1 as an executable object: storage servers
packetize their shards, an arrival model interleaves the flows onto the
ingress link, a switch topology runs MergeMarathon at every hop, an optional
delivery model jitters packet order (bounded displacement — real networks
reorder), and the streaming server recovers the global sort.

The datapath is columnar end to end: flows emit one
:class:`~repro.net.wire.WireBatch`, the hop-graph scheduler
(:func:`repro.net.topology.run_graph`) moves batches between hops, epoch
handoff slices and re-tags columns, and the server ingests the delivered
batch directly — per-object :class:`~repro.net.packet.Packet` lists exist
only at the boundary for the faithful reference and the packet-level tests.
``engine`` selects the hop implementation (``"fused"`` batched, ``"segment"``
pre-fusion per-segment loops, ``"faithful"`` element-at-a-time Alg. 3) —
all three are property-tested byte-identical on the wire.

Ranges come from the control plane in one of three ``range_mode`` settings
(:mod:`repro.net.control`): ``"static"`` equal-width (paper Alg. 2),
``"oracle"`` full-data quantile splitters, or ``"sampled"`` — the adaptive
plane that estimates ranges online and may re-partition mid-stream.  A
re-partition closes the current *epoch*: the fabric drains (Alg. 3's flush
passes), new ranges are installed, and subsequent packets route in a fresh
epoch whose segments get distinct virtual ids; the server then k-way merges
the per-(epoch, segment) outputs instead of concatenating
(``final_merge``) — so a bad or stale estimate can cost balance, never
correctness.

The egress is a :class:`~repro.net.egress.ServerPool`: ``num_servers=``
shards the delivered stream by segment affinity across independent
streaming servers (each running the bounded-reorder/run-merge logic on only
its range shard) and a distributed merge reassembles the global order —
``num_servers=1`` degenerates to the classic single server.

The load-bearing invariant, checked by ``verify=True`` and the test matrix:
for any topology × interleave × delivery × range mode × engine ×
``num_servers``, the server's output equals ``np.sort(input)``, and the
per-(epoch, segment) delivered multisets equal the single-switch reference.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.hostmem import keep_freed_memory
from repro.obs.telemetry import int_summary
from repro.obs.trace import NULL_TRACER

from ..core.partition import (
    WIDE_KEY_BYTES,
    key_words,
    quantile_ranges,
    set_ranges,
    set_ranges_wide,
    words_to_keys,
)
from .control import RANGE_MODES, AdaptiveControlPlane, ControlPlane, ranges_valid
from .egress import ServerPool
from .engine import HopStats
from .faults import FaultPlan, parse_fault_plan
from .flow import interleave_batch, split_flows
from .packet import DEFAULT_PAYLOAD, Packet
from .server import StreamingServer
from .topology import make_topology
from .wire import (
    WireBatch,
    concat_batches,
    packetize_batch,
    ragged_gather,
    segment_streams_batch,
)


@dataclasses.dataclass(eq=False)  # ndarray fields: generated __eq__ would raise
class PipelineResult:
    """Everything one :func:`run_pipeline` run produced (sorted stream,
    per-hop stats, egress timing, optional telemetry/network report)."""

    output: np.ndarray
    passes: list[int]  # per-(epoch, segment) merge passes (server contract)
    hop_stats: list[HopStats]
    segment_multisets: list[np.ndarray]  # delivered per-(epoch, segment) streams
    max_reorder_depth: int
    server_seconds: float  # egress wall-clock: slowest server + pool merge
    n: int
    range_mode: str = "width"
    num_epochs: int = 1
    ranges_history: list[np.ndarray] = dataclasses.field(default_factory=list)
    engine: str = "fused"
    delivered: WireBatch | None = None  # the wire as the server saw it
    num_servers: int = 1
    merge_backend: str = "numpy"  # run-merge engine: "numpy" ladder | "arena"
    per_server_seconds: list[float] = dataclasses.field(default_factory=list)
    pool_merge_seconds: float = 0.0
    server_keys: list[int] = dataclasses.field(default_factory=list)
    server_imbalance: float = 1.0  # peak-over-mean per-server key load
    # Metrics snapshot (+ INT column summary) when the run was observed;
    # None on an uninstrumented run — never part of output equality.
    telemetry: dict | None = None
    # Network timing report (per-link LinkStats + makespan) when a
    # NetworkConfig drove the run; None on a timeless run.
    network: "object | None" = None
    # Server-side recovery counters (non-zero only with recovery mode).
    dup_packets_dropped: int = 0
    spilled_packets: int = 0
    spilled_keys: int = 0
    # Record mode (a payload table attached): the payload rows permuted
    # into key order, and the stable sort permutation that produced them —
    # ``sorted_payload = payload[payload_row_order]``, gathered exactly
    # once at egress.  None for key-only runs.
    sorted_payload: np.ndarray | None = None
    payload_row_order: np.ndarray | None = None
    # Fail-open recovery counters (non-zero only under a fault plan): hops
    # the plan killed/degraded (summed over epochs), shard failovers the
    # pool performed, and corrupted range tables replaced by the static
    # fallback.  The sorted stream itself is byte-identical regardless.
    fault_hops_dead: int = 0
    fault_hops_degraded: int = 0
    servers_failed_over: int = 0
    range_fallbacks: int = 0


def jitter_delivery(
    packets: list[Packet], window: int, seed: int = 0
) -> list[Packet]:
    """Bounded-displacement reorder modelling in-network jitter.

    Each packet's departure priority is its index plus **integer** noise
    drawn uniformly from ``[0, window)``; the sort is stable, so ties keep
    their original order and an inversion needs a *strict* priority
    deficit: packet ``j`` can pass packet ``i < j`` only when
    ``j - i < noise_i - noise_j <= window - 1``.  Every packet therefore
    lands strictly less than ``window`` positions from where it started —
    including at shard edges — which is the bound a receiver sizes its
    reorder buffer against.  (The earlier float-noise draw made the edge
    case unprovable: real-valued priorities never tie, so the displacement
    bound rested on measure-zero luck rather than the stable-sort
    guarantee, and the occupancy tests carried slack to cover it.)
    """
    if window <= 0:
        return list(packets)
    rng = np.random.default_rng(seed)
    pri = np.arange(len(packets), dtype=np.int64) + rng.integers(
        0, window, len(packets)
    )
    return [packets[i] for i in np.argsort(pri, kind="stable")]


def jitter_delivery_batch(
    batch: WireBatch, window: int, seed: int = 0
) -> WireBatch:
    """Columnar :func:`jitter_delivery`: the same per-packet priorities,
    applied as one packet-granular gather of the key columns."""
    if window <= 0:
        return batch
    starts = batch.packet_starts()
    rng = np.random.default_rng(seed)
    pri = np.arange(starts.size, dtype=np.int64) + rng.integers(
        0, window, starts.size
    )
    order = np.argsort(pri, kind="stable")
    sizes = np.diff(np.concatenate([starts, [len(batch)]]))
    return batch.take(ragged_gather(starts[order], sizes[order]))


def run_pipeline(
    values: np.ndarray,
    *,
    topology: str = "single",
    num_flows: int = 4,
    payload_size: int = DEFAULT_PAYLOAD,
    num_segments: int = 16,
    segment_length: int = 32,
    max_value: int | None = None,
    control: ControlPlane | None = None,
    range_mode: str | None = None,
    adaptive: AdaptiveControlPlane | None = None,
    interleave_mode: str = "round_robin",
    seed: int = 0,
    faithful: bool = False,
    backend: str = "numpy",
    engine: str | None = None,
    k: int = 10,
    jitter_window: int = 0,
    reorder_capacity: int | None = None,
    network=None,
    recovery: bool | None = None,
    num_servers: int = 1,
    merge_backend: str = "numpy",
    pool_backend: str = "numpy",
    fault_plan: "FaultPlan | str | None" = None,
    replay_packets: int | None = None,
    payload: np.ndarray | None = None,
    verify: bool = False,
    tracer=None,
    metrics=None,
    int_telemetry: bool = False,
    **topo_kw,
) -> PipelineResult:
    """Drive the full storage→switch→server datapath over ``values``.

    Exactly one range source applies: ``range_mode`` (``"oracle"``,
    ``"sampled"``, ``"static"``), an explicit ``control`` plane, or the
    default equal-width :class:`ControlPlane`.  ``adaptive`` optionally
    supplies a pre-configured :class:`AdaptiveControlPlane` for
    ``range_mode="sampled"``; it is consumed by the run (single-use).
    ``engine`` picks the hop implementation; unset it derives from
    ``faithful``/the default fused path.  ``num_servers`` shards the egress
    across a segment-affinity :class:`~repro.net.egress.ServerPool`
    (``num_servers=1`` is the classic single streaming server); the output
    is byte-identical for every ``num_servers`` — only the makespan and the
    per-server load change.  ``merge_backend`` picks each server's run-merge
    engine (``"numpy"`` eager ladder or the device-resident ``"arena"``
    tournament — byte-identical ``(output, passes)``, the
    ``server_throughput`` bench section measures the difference);
    ``pool_backend`` picks the pool's distributed merge (``"numpy"`` or
    the ``"shard_map"`` collective over the devices present).

    Observability (all opt-in and output-transparent — the sorted stream,
    passes, and epoch structure are byte-identical instrumented or not):
    every span (pipeline → ``pipeline:ingress`` → epoch → hop or
    ``epoch:*`` stages → ``server<s>:*``/merge lanes → ``pipeline:result``)
    reaches a running JAX profiler's trace, with or without a ``tracer``;
    ``tracer`` (a :class:`repro.obs.Tracer`) also keeps them for export and
    changes nothing else about the run.  ``metrics`` (a
    :class:`repro.obs.MetricsRegistry`) accumulates the dataplane
    counters/gauges/histograms, whose snapshot lands in
    ``PipelineResult.telemetry``; ``int_telemetry=True`` stamps INT-style
    per-hop metadata columns onto the wire (``fused`` engine only), exposed
    on ``result.delivered.int_meta`` and summarized in the telemetry dict.
    Telemetry comes from these two alone.

    ``network`` (a :class:`~repro.net.timing.NetworkConfig`) runs the fabric
    under the per-link timing model: every link gets a latency / bandwidth /
    bounded-buffer budget, interior loss is absorbed by per-link ARQ (it
    costs time, never content), and the **egress link delivers the raw
    wire** — retransmit duplicates and late-beyond-jitter packets included —
    so the egress pool defaults to ``recovery=True`` (seq dedup + spill) and
    still yields output byte-identical to the lossless run.  The per-link
    :class:`~repro.net.timing.LinkStats` and the network makespan land in
    ``PipelineResult.network``; ``recovery`` can be forced on/off explicitly
    (off + a lossy egress link raises on the first duplicate — the PR-4
    detection behaviour).

    ``fault_plan`` (a :class:`~repro.net.faults.FaultPlan` or its CLI
    string form, e.g. ``"crash:leaf0@0;server_crash:1@0.5"``) injects
    deterministic faults and exercises the fail-open recovery machinery:
    dead hops are rerouted around (ingress flows rehash onto alive leaves,
    interior consumers absorb dead parents' feeds), degraded hops forward
    in pass-through mode (the paper's plain-sort baseline — unsorted but
    lossless), flapped links take the extra latency/loss through the
    timing model's ARQ, crashed egress shards fail over to the nearest
    alive neighbor (which re-ingests the dead shard's history from a
    replay buffer bounded by ``replay_packets``; ``None`` = unbounded),
    and a corrupted range table is detected and replaced by the static
    equal-width fallback.  Every *survivable* plan (one that leaves the
    egress hop, at least one ingress hop, and — for shard crashes — an
    adoptive server alive) yields output byte-identical to the fault-free
    run; only throughput and load balance degrade.  Recovery counters land
    on the result (``fault_hops_dead``, ``fault_hops_degraded``,
    ``servers_failed_over``, ``range_fallbacks``).

    ``payload`` attaches a record table (one row per key, any trailing
    shape): the fabric sorts **records**, not bare keys.  The payload bytes
    never ride the wire — each key carries its input-row index as a wire
    column (``fused`` and ``device`` engines only), the server sorts keys
    packed with their row (ties resolve by arrival order, i.e. a stable
    sort), and the table is gathered exactly once at egress into
    ``PipelineResult.sorted_payload``.  For integer keys the key domain
    must leave room for the row bits: ``max_value < 2**(63 -
    ceil(log2(n)))``.

    Wide keys: ``values`` may be an ``(n, B)`` uint8 array of keys of ``B``
    = 9 to 16 bytes, ordered as unsigned bytes (memcmp, the Sort
    Benchmark's order), with ``payload`` an ``(n, P)`` array.  They run on
    ``engine="device"`` with ``merge_backend="arena"`` and Alg. 2's static
    equal-width ranges over ``[0, 256**B)``
    (:func:`repro.core.partition.set_ranges_wide`) alone; every other
    engine, range source and observer refuses them
    (:func:`_run_pipeline_wide`).  Each key rides as ``ceil(B/4)`` int32
    sort words (:func:`repro.core.partition.key_words`) with its input row
    as the last sort word, so ties resolve by input row (a stable sort).
    ``output`` holds the sorted keys, ``(n, B)`` uint8; ``sorted_payload``
    the payload rows in that order, gathered once on the host in the span
    ``pipeline:payload``; ``delivered`` the wire with the words as its
    ``(n, W)`` values and each key's ``row_index`` and ``segment_id``.  A
    wide-key job sets the process's allocator to keep the memory it frees
    (:func:`repro.hostmem.keep_freed_memory`), so the next job reuses its
    host columns' pages.
    """
    values = np.asarray(values)
    if values.ndim == 2:
        return _run_pipeline_wide(
            values,
            topology=topology, num_flows=num_flows, payload_size=payload_size,
            num_segments=num_segments, segment_length=segment_length,
            interleave_mode=interleave_mode, seed=seed, backend=backend,
            k=k, num_servers=num_servers, pool_backend=pool_backend,
            reorder_capacity=reorder_capacity, payload=payload,
            verify=verify, tracer=tracer, topo_kw=topo_kw,
            refused={
                "engine": (
                    (engine or "fused") if engine != "device" else None
                ),
                "faithful": faithful or None,
                "merge_backend": (
                    merge_backend if merge_backend != "arena" else None
                ),
                "range_mode": range_mode if range_mode != "static" else None,
                "max_value": max_value,
                "control": control,
                "adaptive": adaptive,
                "network": network,
                "recovery": recovery or None,
                "fault_plan": fault_plan or None,
                "jitter_window": jitter_window or None,
                "metrics": metrics,
                "int_telemetry": int_telemetry or None,
            },
        )
    values = np.asarray(values, dtype=np.int64)
    if max_value is None:
        max_value = int(values.max(initial=0))
    if range_mode is not None:
        if range_mode not in RANGE_MODES:
            raise ValueError(
                f"unknown range_mode {range_mode!r}; options: {RANGE_MODES}"
            )
        if control is not None:
            raise ValueError("pass either control= or range_mode=, not both")
    if adaptive is not None and range_mode != "sampled":
        raise ValueError('adaptive= requires range_mode="sampled"')
    if faithful and engine is not None and engine != "faithful":
        raise ValueError(
            f"faithful=True conflicts with engine={engine!r}; pass one"
        )
    engine = engine or ("faithful" if faithful else "fused")
    if recovery is None:
        # A timed network's egress link is raw (duplicates, late
        # retransmits) — the pool must heal it by default.
        recovery = network is not None
    if isinstance(fault_plan, str):
        fault_plan = parse_fault_plan(fault_plan, seed=seed)
    if fault_plan is not None and not fault_plan:
        fault_plan = None  # empty plan == no plan
    fault_counters = {"dead": 0, "degraded": 0, "range_fallbacks": 0}

    tr = tracer or NULL_TRACER
    with tr.span("pipeline", cat="pipeline", n=int(values.size)):
        nbits = 0
        if payload is not None:
            payload = np.asarray(payload)
            if payload.shape[0] != int(values.size):
                raise ValueError(
                    f"payload rows {payload.shape[0]} != "
                    f"{values.size} keys"
                )
            nbits = max(1, int(values.size - 1).bit_length())
            if int(max_value) >= 1 << (63 - nbits):
                raise ValueError(
                    f"cannot pack {values.size} payload rows next to keys "
                    f"up to {max_value} in 63 bits"
                )
        with tr.span(
            "pipeline:ingress", cat="pipeline", keys=int(values.size)
        ):
            flows = split_flows(values, num_flows, payload_size)
            arrivals = interleave_batch(flows, interleave_mode, seed=seed)
            if payload is not None:
                # Thread each key's input row through the same shard split
                # and interleave schedule the keys took (the schedule
                # depends only on flow sizes and the seed), so the row
                # column lands on its key's arrival row.  The payload table
                # itself stays put until the one egress gather.
                rows = interleave_batch(
                    split_flows(
                        np.arange(values.size, dtype=np.int64),
                        num_flows,
                        payload_size,
                    ),
                    interleave_mode,
                    seed=seed,
                )
                arrivals = arrivals.with_row_index(rows.values)

        def _run_topology(ranges: np.ndarray, batch: WireBatch, epoch: int = 0):
            ef = fault_plan.at_epoch(epoch) if fault_plan is not None else None
            if ef is not None and ef.range_corrupt:
                bad = ef.corrupt_ranges(ranges)
                if not ranges_valid(bad, num_segments, max_value):
                    # Fail-open control plane: a table that fails the
                    # validity check is never programmed — fall back to
                    # the static Alg. 2 equal-width table for this epoch
                    # (balance degrades; the sort does not).
                    ranges = set_ranges(max_value, num_segments)
                    fault_counters["range_fallbacks"] += 1
                    tr.instant(
                        "fault:range_table", cat="fault", epoch=epoch
                    )
                    if metrics is not None:
                        metrics.counter("fault_range_fallbacks").inc()
                else:  # pragma: no cover — corruption is always detectable
                    ranges = bad
            topo = make_topology(
                topology,
                num_segments=num_segments,
                segment_length=segment_length,
                max_value=max_value,
                ranges=ranges,
                faithful=faithful,
                backend=backend,
                engine=engine,
                payload_size=payload_size,
                **topo_kw,
            )
            if ef is not None and ef.any_dataplane:
                for node in topo.graph().nodes:
                    st = ef.hop_state(node.name)
                    if st == "dead":
                        fault_counters["dead"] += 1
                    elif st == "degraded":
                        fault_counters["degraded"] += 1
            res = topo.run_batch(
                batch,
                tracer=tracer,
                metrics=metrics,
                int_telemetry=int_telemetry,
                network=network,
                faults=ef,
            )
            if network is None:
                out, stats = res
                return out, stats, None
            return res  # (delivered, stats, NetworkReport)

        if range_mode == "sampled":
            plane = adaptive or AdaptiveControlPlane(
                num_segments, max_value, seed=seed,
                tracer=tracer, metrics=metrics,
            )
            with tr.span("control:split_epochs", cat="control"):
                epochs = plane.split_epochs(arrivals)
            delivered_epochs: list[WireBatch] = []
            hop_stats: list[HopStats] = []
            ranges_history: list[np.ndarray] = []
            net_reports = []
            for e, (ranges_e, sub) in enumerate(epochs):
                with tr.span(f"epoch:{e}", cat="pipeline", keys=len(sub)):
                    out, stats, rep = _run_topology(ranges_e, sub, epoch=e)
                delivered_epochs.append(out.with_epoch(e, num_segments))
                hop_stats.extend(
                    dataclasses.replace(st, name=f"e{e}:{st.name}")
                    for st in stats
                )
                if rep is not None:
                    for lst in rep.links:
                        lst.name = f"e{e}:{lst.name}"
                    net_reports.append(rep)
                ranges_history.append(ranges_e)
            if net_reports:
                from .timing import merge_reports

                net_report = merge_reports(net_reports)
            else:
                net_report = None
            delivered = concat_batches(delivered_epochs)
            eff_segments = num_segments * len(epochs)
            # Epoch handoff re-shards the virtual ids across the pool (empty
            # epochs were dropped, so slice the map to the ids actually on
            # the wire — the tiling is per-epoch, so the prefix is exact).
            affinity = plane.pool_affinity(num_servers)[:eff_segments]
            mode_str = "sampled"
        else:
            if range_mode == "oracle":
                ranges = quantile_ranges(values, num_segments, max_value)
                mode_str = "oracle"
            elif range_mode == "static":
                ranges = set_ranges(max_value, num_segments)
                mode_str = "static"
            else:
                plane = control or ControlPlane()
                ranges = plane.ranges(values, num_segments, max_value)
                mode_str = plane.mode
            with tr.span("epoch:0", cat="pipeline", keys=len(arrivals)):
                delivered, hop_stats, net_report = _run_topology(
                    ranges, arrivals
                )
            ranges_history = [ranges]
            eff_segments = num_segments
            affinity = None

        if jitter_window:
            delivered = jitter_delivery_batch(
                delivered, jitter_window, seed=seed + 1
            )

        # Shard-crash fractions resolve against the delivered packet count:
        # ``at_fraction=0.5`` kills the shard after half the wire's packets
        # have been demuxed (mid-stream, deterministically).
        crash_sched = (
            fault_plan.server_crashes(num_servers)
            if fault_plan is not None
            else []
        )
        if crash_sched:
            total_pkts = int(delivered.packet_starts().size)
            crash_sched = [
                (s, int(round(frac * total_pkts))) for s, frac in crash_sched
            ]
        pool = ServerPool(
            num_segments,
            num_servers,
            num_epochs=eff_segments // num_segments,
            k=k,
            reorder_capacity=reorder_capacity,
            affinity=affinity,
            merge_backend=merge_backend,
            pool_backend=pool_backend,
            recovery=recovery,
            crash_schedule=crash_sched or None,
            replay_packets=replay_packets,
            tracer=tracer,
            metrics=metrics,
        )
        if payload is not None and delivered.row_index is None:
            raise ValueError(
                f"engine {engine!r} dropped the payload row column"
            )
        grouped = getattr(delivered, "grouped_values", None)
        if (
            grouped is not None
            and not recovery
            and not crash_sched
            and (reorder_capacity is None or reorder_capacity >= 1)
            and eff_segments == num_segments
        ):
            # Compiled-epoch fast path: the device delivery already carries
            # each segment's emission stream and its run breaks — feed the
            # arenas directly instead of re-deriving packet boundaries.
            seg_counts = delivered.seg_counts
            flags = np.asarray(delivered.run_flags, dtype=bool)
            if payload is not None:
                grouped = (grouped << nbits) | delivered.grouped_rows
                # Row tie-breaks can split runs the key-only flags did not
                # see; one vectorized compare re-detects them.
                flags = np.zeros(grouped.size, dtype=bool)
                seg_starts = np.concatenate([[0], np.cumsum(seg_counts)[:-1]])
                flags[seg_starts[seg_counts > 0]] = True
                flags[1:] |= grouped[1:] < grouped[:-1]
            pool.ingest_grouped(grouped, seg_counts, flags)
        elif payload is not None:
            # Pack (key << rowbits) | row: key order is preserved and ties
            # resolve by input row, so the server's merge is a stable sort
            # of the records without ever touching the payload bytes.
            pool.ingest_batch(
                WireBatch(
                    (delivered.values << nbits) | delivered.row_index,
                    delivered.flow_id,
                    delivered.seq,
                    delivered.segment_id,
                    epoch=delivered.epoch,
                )
            )
        else:
            pool.ingest_batch(delivered)
        out, passes = pool.finish()
        row_order = None
        sorted_payload = None
        if payload is not None:
            row_order = out & ((1 << nbits) - 1)
            out = out >> nbits
            sorted_payload = payload[row_order]

        telemetry = None
        if metrics is not None or delivered.int_meta is not None:
            telemetry = metrics.snapshot() if metrics is not None else {}
            if delivered.int_meta is not None:
                telemetry["int"] = int_summary(delivered.int_meta)

        with tr.span("pipeline:result", cat="pipeline"):
            # Reorder-buffer-corrected per-segment streams, for multiset
            # invariants.  (jitter permutes packets; segment_streams gives
            # raw arrival order, which is fine — invariants are
            # multiset-level.)
            seg_ms = segment_streams_batch(delivered, eff_segments)
            result = PipelineResult(
                output=out,
                passes=passes,
                hop_stats=hop_stats,
                segment_multisets=seg_ms,
                max_reorder_depth=pool.max_reorder_depth,
                server_seconds=pool.makespan_seconds,
                n=int(values.size),
                range_mode=mode_str,
                num_epochs=len(ranges_history),
                ranges_history=ranges_history,
                engine=engine,
                delivered=delivered,
                num_servers=num_servers,
                merge_backend=merge_backend,
                per_server_seconds=list(pool.per_server_seconds),
                pool_merge_seconds=pool.merge_seconds,
                server_keys=pool.server_keys,
                server_imbalance=pool.server_imbalance,
                telemetry=telemetry,
                network=net_report,
                dup_packets_dropped=pool.dup_packets_dropped,
                spilled_packets=pool.spilled_packets,
                spilled_keys=pool.spilled_keys,
                sorted_payload=sorted_payload,
                payload_row_order=row_order,
                fault_hops_dead=fault_counters["dead"],
                fault_hops_degraded=fault_counters["degraded"],
                servers_failed_over=pool.servers_failed_over,
                range_fallbacks=fault_counters["range_fallbacks"],
            )

    if verify:
        np.testing.assert_array_equal(out, np.sort(values))
        if payload is not None:
            np.testing.assert_array_equal(
                row_order, np.argsort(values, kind="stable")
            )
    return result


#: What the wide-key path runs on; every other choice is refused.
WIDE_KEY_PATH = (
    'engine="device", merge_backend="arena", range_mode="static", '
    'pool_backend="numpy", with no metrics, network, recovery, faults, '
    "jitter or INT telemetry"
)


def _run_pipeline_wide(
    keys: np.ndarray,
    *,
    topology: str,
    num_flows: int,
    payload_size: int,
    num_segments: int,
    segment_length: int,
    interleave_mode: str,
    seed: int,
    backend: str,
    k: int,
    num_servers: int,
    pool_backend: str,
    reorder_capacity: int | None,
    payload: np.ndarray | None,
    verify: bool,
    tracer,
    topo_kw: dict,
    refused: dict,
) -> PipelineResult:
    """:func:`run_pipeline` for ``(n, B)`` uint8 keys (its docstring's
    "Wide keys").  ``refused`` holds the run's other options, each None
    where unset; any that is set raises, naming the supported path."""
    given = sorted(name for name, v in refused.items() if v is not None)
    if pool_backend != "numpy":
        given.append("pool_backend")
    if reorder_capacity is not None and reorder_capacity < 1:
        given.append("reorder_capacity")
    if given:
        raise ValueError(
            f"keys wider than 64 bits do not support {', '.join(given)}; "
            f"they run on {WIDE_KEY_PATH}"
        )
    keys = np.asarray(keys)
    n, key_bytes = keys.shape
    if keys.dtype != np.uint8 or key_bytes not in WIDE_KEY_BYTES:
        raise ValueError(
            f"wide keys are (n, 9..16) uint8, got {keys.shape} {keys.dtype}"
        )
    if n == 0:
        raise ValueError("a wide-key job needs at least one key")
    if payload is not None:
        payload = np.asarray(payload)
        if payload.shape[0] != n:
            raise ValueError(f"payload rows {payload.shape[0]} != {n} keys")
    # A records job allocates and frees gigabytes of host columns; the
    # process keeps them for its next job instead of faulting them in anew.
    keep_freed_memory()
    tr = tracer or NULL_TRACER
    with tr.span("pipeline", cat="pipeline", n=n):
        with tr.span("pipeline:ingress", cat="pipeline", keys=n):
            words = key_words(keys)
            # The flows carry row numbers; each key's words follow its row.
            rows = interleave_batch(
                split_flows(np.arange(n, dtype=np.int64), num_flows,
                            payload_size),
                interleave_mode,
                seed=seed,
            )
            arrivals = WireBatch(
                np.take(words, rows.values, axis=0), rows.flow_id, rows.seq,
                rows.segment_id, row_index=rows.values,
            )
        ranges = set_ranges_wide(key_bytes, num_segments)
        topo = make_topology(
            topology,
            num_segments=num_segments,
            segment_length=segment_length,
            max_value=256**key_bytes - 1,
            ranges=ranges,
            backend=backend,
            engine="device",
            payload_size=payload_size,
            **topo_kw,
        )
        with tr.span("epoch:0", cat="pipeline", keys=n):
            delivered, hop_stats = topo.run_batch(arrivals, tracer=tracer)
        pool = ServerPool(
            num_segments,
            num_servers,
            k=k,
            reorder_capacity=reorder_capacity,
            merge_backend="arena",
            tracer=tracer,
        )
        pool.ingest_grouped(
            delivered.grouped_values, delivered.seg_counts,
            delivered.run_flags,
        )
        out, passes = pool.finish()
        row_order = out[:, -1].astype(np.int64)
        output = words_to_keys(out[:, :-1], key_bytes)
        sorted_payload = None
        if payload is not None:
            with tr.span(
                "pipeline:payload", cat="pipeline", bytes=int(payload.nbytes)
            ):
                # ``take`` copies whole rows, several times faster than
                # fancy indexing for rows of a few dozen bytes.
                sorted_payload = np.take(payload, row_order, axis=0)
        with tr.span("pipeline:result", cat="pipeline"):
            # A segment's keys cross the wire in their emission order, the
            # order of the delivery's grouped stream: its slices are the
            # per-segment streams, without a host sort.
            result = PipelineResult(
                output=output,
                passes=passes,
                hop_stats=hop_stats,
                segment_multisets=np.split(
                    delivered.grouped_values[:, :-1],
                    np.cumsum(delivered.seg_counts)[:-1],
                ),
                max_reorder_depth=pool.max_reorder_depth,
                server_seconds=pool.makespan_seconds,
                n=n,
                range_mode="static",
                ranges_history=[ranges],
                engine="device",
                delivered=delivered,
                num_servers=num_servers,
                merge_backend="arena",
                per_server_seconds=list(pool.per_server_seconds),
                pool_merge_seconds=pool.merge_seconds,
                server_keys=pool.server_keys,
                server_imbalance=pool.server_imbalance,
                sorted_payload=sorted_payload,
                payload_row_order=row_order,
            )
    if verify:
        want = np.lexsort(keys.T[::-1])
        np.testing.assert_array_equal(row_order, want)
        np.testing.assert_array_equal(output, keys[want])
    return result


def plain_stream_sort(
    values: np.ndarray,
    payload_size: int = DEFAULT_PAYLOAD,
    k: int = 10,
    *,
    tracer=None,
) -> tuple[np.ndarray, list[int], float]:
    """Switchless baseline: raw packets straight into the streaming server
    (one segment, no port numbers to demux by).  Returns
    ``(sorted, passes, server_seconds)``.

    Timing goes through the tracer's ``timed`` primitive (the repo's single
    wall-clock source); with the default null tracer it measures without
    recording, so the returned seconds are identical either way.
    """
    values = np.asarray(values, dtype=np.int64)
    batch = packetize_batch(values, payload_size, segment_id=0)
    server = StreamingServer(1, k=k, tracer=tracer, name="baseline")
    with (tracer or NULL_TRACER).timed(
        "baseline:server", cat="server"
    ) as t:
        server.ingest_batch(batch)
        out, passes = server.finish()
    return out, passes, t.seconds
