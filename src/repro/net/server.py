"""Streaming computation server: sort overlapped with packet arrival.

The paper's server (Alg. 1) buffers the whole stream, then runs k-way
natural merge sort.  A real compute server does not wait: it consumes packets
as they land.  :class:`StreamingServer` keeps, per segment (port number):

* a **bounded reorder buffer** — packets carry per-segment sequence numbers;
  the network may deliver them out of order, and the buffer restores emission
  order before any key is looked at (capacity overflow raises: the knob is
  the memory the NIC driver would dedicate per port);

With ``recovery=True`` the server heals a lossy wire instead of refusing it
(the raw egress link of :mod:`repro.net.timing` delivers retransmit
duplicates and late-beyond-jitter packets): duplicate sequence numbers are
counted and dropped rather than raised, and when the bounded reorder buffer
overflows the youngest buffered packet is **spilled** — fed out of band to
the run detector as its own run (sortedness and the multiset are preserved;
the cost is shorter runs, i.e. more merge work) with its seq remembered so
the in-order cursor steps over it and late copies still dedupe.  Genuinely
missing packets still fail ``finish()``: recovery never invents keys.
Additional per-server state:
* incremental **natural-run detection** across packet boundaries — the
  switch guarantees ≥L-length ascending runs, which the detector recovers
  exactly as Alg. 1 would on the full stream;
* an **eager k-way merge ladder** — closed runs enter level 0; whenever a
  level accumulates ``k`` runs they merge into one run a level up (the same
  k-sets Alg. 1's passes form, executed as soon as their inputs exist, so
  merge work overlaps with arrival instead of following it).

Two ``merge_backend``s drain the detected runs:

* ``"numpy"`` (default) — the eager host ladder above: runs are Python-held
  arrays, every merge a pairwise ``merge_two`` (one ``searchsorted`` +
  scatter per pair).
* ``"arena"`` — the device-resident run-arena engine: each segment's runs
  live as adjacent slices of one contiguous buffer
  (:class:`repro.core.runs.RunArena`; ingest appends columnarly, zero
  per-run Python), and at drain time the whole segment becomes one padded
  tournament matrix merged on device
  (:func:`repro.core.mergesort.merge_runs_flat` →
  :func:`repro.kernels.ops.merge_tournament` — each ladder level is one
  round of the log-depth bitonic merge network over *all* pairs of the
  level at once).  Output and pass counts are byte-identical to the numpy
  ladder — only the wall-clock changes (the ``server_throughput`` bench
  section gates the arena at ≥2× the ladder on 1M keys).

Ingestion speaks both wire formats: per-object packets (:meth:`ingest`) and
columnar :class:`~repro.net.wire.WireBatch` streams (:meth:`ingest_batch`),
whose fast path feeds each in-order segment's keys through the vectorized
run detector in one call — the NIC demux as a mask, not a packet loop.

``finish()`` returns the same ``(sorted, per-segment passes)`` contract as
:func:`repro.core.mergesort.server_sort`, so benchmarks can swap one for the
other.  With ``final_merge=True`` the per-segment outputs are k-way merged
instead of concatenated — required when segments are *epoched* by the
adaptive control plane (:mod:`repro.net.control`): ranges from different
epochs overlap, so segment order no longer implies key order.  The reported pass count is ``merge_passes(runs, k)`` — provably equal
to ``merge_sort``'s measured pass count on the identical stream (asserted by
``benchmarks/run.py bench_theory`` and the net test-suite).
"""

from __future__ import annotations

import numpy as np

from repro.obs.trace import NULL_TRACER

from ..core.mergesort import merge_runs, merge_runs_batched, merge_runs_flat
from ..core.runs import RunArena, merge_passes, run_starts
from .packet import Packet
from .wire import ragged_gather

#: Run-merge engines a streaming server can drain with.
MERGE_BACKENDS = ("numpy", "arena")


class StreamingServer:
    """Consumes tagged packets incrementally; emits the global sort."""

    def __init__(
        self,
        num_segments: int,
        k: int = 10,
        reorder_capacity: int | None = None,
        final_merge: bool = False,
        merge_backend: str = "numpy",
        *,
        recovery: bool = False,
        tracer=None,
        metrics=None,
        name: str = "server0",
        lane: int = 1,
    ) -> None:
        if num_segments <= 0:
            raise ValueError("num_segments must be positive")
        if merge_backend not in MERGE_BACKENDS:
            raise ValueError(
                f"unknown merge_backend {merge_backend!r}; "
                f"options: {', '.join(MERGE_BACKENDS)}"
            )
        self.num_segments = num_segments
        self.k = k
        self.reorder_capacity = reorder_capacity
        self.final_merge = final_merge
        self.merge_backend = merge_backend
        self.recovery = recovery
        self.name = name
        self.lane = lane  # trace lane (Chrome tid): pool servers get 1+s
        self._tr = tracer or NULL_TRACER
        self._metrics = metrics
        # Run lengths buffer here as plain ints; one vectorized histogram
        # observe at finish() keeps the per-run hot path free of registry
        # lookups (the tracer-overhead CI gate counts on this).
        self._run_len_buf: list[int] = []
        S = num_segments
        self._pending: list[dict[int, np.ndarray]] = [{} for _ in range(S)]
        self._next_seq = [0] * S
        self._cur: list[list[np.ndarray]] = [[] for _ in range(S)]
        self._tail: list[int | None] = [None] * S
        self._levels: list[list[list[np.ndarray]]] = [[] for _ in range(S)]
        self._run_count = [0] * S
        self._arenas: list[RunArena] | None = (
            [RunArena() for _ in range(S)] if merge_backend == "arena" else None
        )
        self._ingested = 0
        self.max_reorder_depth = 0  # observability: worst buffer occupancy
        # Recovery-mode state: seqs spilled out of band (kept until the
        # in-order cursor passes them, so late duplicates still dedupe).
        self._spilled: list[set[int]] = [set() for _ in range(S)]
        self.dup_packets_dropped = 0
        self.spilled_packets = 0
        self.spilled_keys = 0

    @property
    def keys_ingested(self) -> int:
        """Keys fed past the reorder buffer so far (load observability —
        the egress pool's per-server share of the stream)."""
        return self._ingested

    def grow(self, m: int) -> None:
        """Failover adoption: append ``m`` fresh segments (ports).

        The pool's shard-failover path calls this on the adopting server so
        a dead neighbor's segment range gets fresh per-port state (reorder
        buffer, seq cursor, run detector, merge ladder) appended after the
        adopter's own — the replayed history then rebuilds exactly the
        state the dead shard had, because run detection and the ladder are
        deterministic in ingestion order.
        """
        if m <= 0:
            raise ValueError("grow() needs a positive segment count")
        self.num_segments += m
        self._pending.extend({} for _ in range(m))
        self._next_seq.extend([0] * m)
        self._cur.extend([] for _ in range(m))
        self._tail.extend([None] * m)
        self._levels.extend([] for _ in range(m))
        self._run_count.extend([0] * m)
        self._spilled.extend(set() for _ in range(m))
        if self._arenas is not None:
            self._arenas.extend(RunArena() for _ in range(m))

    # -- ingestion ------------------------------------------------------
    def ingest(self, packet: Packet) -> None:
        self._ingest_payload(packet.segment_id, packet.seq, packet.payload)

    def _ingest_payload(self, sid: int, seq: int, payload: np.ndarray) -> None:
        if not 0 <= sid < self.num_segments:
            raise ValueError(f"packet with invalid segment id {sid}")
        buf = self._pending[sid]
        if seq < self._next_seq[sid] or seq in buf or seq in self._spilled[sid]:
            if self.recovery:
                # A retransmit whose original also made it: count and drop.
                self.dup_packets_dropped += 1
                if self._metrics is not None:
                    self._metrics.counter(
                        "server_dup_packets", self.name
                    ).inc()
                return
            raise ValueError(
                f"duplicate packet seg={sid} seq={seq}"
            )
        buf[seq] = payload
        depth = len(buf)
        self.max_reorder_depth = max(self.max_reorder_depth, depth)
        if self._metrics is not None:
            # Timeline of buffer occupancy, x = keys ingested so far.
            self._metrics.series("reorder_depth", self.name).append(
                self._ingested, depth
            )
        if self.reorder_capacity is not None and depth > self.reorder_capacity:
            if not self.recovery:
                raise ValueError(
                    f"reorder buffer overflow on segment {sid}: {depth} "
                    f"packets buffered, capacity {self.reorder_capacity}"
                )
            # In-order progress may relieve the pressure before any spill.
            self._drain(sid)
            while len(buf) > self.reorder_capacity:
                self._spill(sid)
        self._drain(sid)

    def _drain(self, sid: int) -> None:
        """Advance the in-order cursor: feed buffered packets, step over
        spilled seqs (their keys are already in the run detector)."""
        buf = self._pending[sid]
        spilled = self._spilled[sid]
        while True:
            nxt = self._next_seq[sid]
            if nxt in buf:
                self._next_seq[sid] = nxt + 1
                self._feed(sid, buf.pop(nxt))
            elif spilled and nxt in spilled:
                spilled.discard(nxt)
                self._next_seq[sid] = nxt + 1
            else:
                return

    def _spill(self, sid: int) -> None:
        """Evict the youngest buffered packet out of band (recovery mode).

        Its keys go straight into the run detector as regular payload — the
        detector's run-break rule keeps the merge ladder's inputs sorted, so
        the final output is byte-identical; the only cost is shorter runs
        (more merge work), the right trade for keys delayed beyond any
        bounded jitter window.  The seq is remembered until the in-order
        cursor passes it so late copies still dedupe.
        """
        buf = self._pending[sid]
        seq = max(buf)
        arr = buf.pop(seq)
        self._spilled[sid].add(seq)
        self.spilled_packets += 1
        self.spilled_keys += int(arr.size)
        if self._metrics is not None:
            self._metrics.counter("server_spilled_packets", self.name).inc()
            self._metrics.counter("server_spilled_keys", self.name).inc(
                int(arr.size)
            )
        self._feed(sid, arr)

    def ingest_batch(self, batch) -> None:
        """Consume a columnar :class:`~repro.net.wire.WireBatch` directly.

        The common case — every segment's packets arrive in sequence order —
        never touches per-packet Python state: each segment's keys are
        gathered with one mask and run through the vectorized run detector
        in a single ``_feed``.  Segments that *did* see reordering (or that
        resume around an earlier partial ingest) fall back to the per-packet
        reorder buffer, packet by packet, byte-identical to :meth:`ingest`.
        """
        n = len(batch)
        if n == 0:
            return
        with self._tr.span(
            f"{self.name}:ingest", cat="server", tid=self.lane, keys=n
        ):
            self._ingest_batch_body(batch, n)

    def _ingest_batch_body(self, batch, n: int) -> None:
        starts = batch.packet_starts()
        bounds = np.concatenate([starts, [n]])
        sizes = np.diff(bounds)
        sids_p = batch.segment_id[starts]
        seqs_p = batch.seq[starts]
        if sids_p.min() < 0 or sids_p.max() >= self.num_segments:
            bad = int(sids_p.min()) if sids_p.min() < 0 else int(sids_p.max())
            raise ValueError(f"packet with invalid segment id {bad}")
        # All grouping below works on per-packet arrays (a few thousand
        # entries), never on per-key columns: the only O(n) work is one
        # ragged gather per in-order segment, over that segment's keys.
        slow: list[int] = []
        for s in np.unique(sids_p):
            s = int(s)
            pmask = sids_p == s
            seqs = seqs_p[pmask]
            # A zero-capacity reorder buffer rejects even in-order packets
            # (per-packet ingest holds each packet at depth 1 before
            # draining) — route through the slow path so it raises the same
            # overflow error.
            in_order = (
                (self.reorder_capacity is None or self.reorder_capacity >= 1)
                and not self._pending[s]
                and not self._spilled[s]
                and np.array_equal(
                    seqs,
                    np.arange(
                        self._next_seq[s], self._next_seq[s] + seqs.size
                    ),
                )
            )
            if not in_order:
                slow.append(s)
                continue
            # The reorder buffer would have held exactly one packet at a
            # time; keep the observability high-water mark consistent.
            self.max_reorder_depth = max(self.max_reorder_depth, 1)
            self._next_seq[s] += int(seqs.size)
            self._feed(
                s, batch.values[ragged_gather(starts[pmask], sizes[pmask])]
            )
        slow_set = set(slow)
        if slow_set:
            for s, a, b in zip(sids_p, bounds[:-1], bounds[1:]):
                if int(s) in slow_set:
                    self._ingest_payload(
                        int(s), int(batch.seq[a]), batch.values[a:b]
                    )

    def ingest_segment(
        self,
        sid: int,
        values: np.ndarray,
        run_starts: np.ndarray | None = None,
    ) -> None:
        """Whole-segment in-order handoff from the compiled-epoch dataplane.

        ``values`` is the segment's complete emission-order stream for the
        epoch — what the reorder buffer would have reassembled from the
        segment's packets — so the packet machinery is skipped entirely.
        ``run_starts`` (payload-relative, ``run_starts[0] == 0``) carries
        the run boundaries the device already detected; the arena backend
        consumes them via :meth:`repro.core.runs.RunArena.feed_runs`, other
        backends re-detect (one vectorized compare).  Byte-identical to
        ingesting the same stream packet by packet in order.
        """
        values = np.asarray(values)
        m = len(values)
        if m == 0:
            return
        if sid < 0 or sid >= self.num_segments:
            raise ValueError(f"packet with invalid segment id {sid}")
        if self._pending[sid] or self._spilled[sid]:
            raise ValueError(
                f"segment {sid} has buffered packets; the grouped handoff "
                "requires a clean in-order stream"
            )
        with self._tr.span(
            f"{self.name}:ingest", cat="server", tid=self.lane, keys=m
        ):
            # The packet path would have held one packet at a time.
            self.max_reorder_depth = max(self.max_reorder_depth, 1)
            if run_starts is not None and self._arenas is not None:
                self._ingested += m
                self._arenas[sid].feed_runs(values, run_starts)
            else:
                self._feed(sid, values)

    def _feed(self, sid: int, arr: np.ndarray) -> None:
        """Continue natural-run detection over one in-order payload."""
        if arr.size == 0:
            return
        self._ingested += int(arr.size)
        if self._arenas is not None:
            # Arena backend: the same run-break rule, applied columnarly —
            # keys append to the segment's flat buffer, boundaries to its
            # offsets table, and the open run continues across payloads.
            self._arenas[sid].feed(arr)
            return
        tail = self._tail[sid]
        if tail is not None and int(arr[0]) < tail:
            self._close_run(sid)
        breaks = np.nonzero(arr[1:] < arr[:-1])[0] + 1
        parts = np.split(arr, breaks)
        for chunk in parts[:-1]:
            self._cur[sid].append(chunk)
            self._close_run(sid)
        self._cur[sid].append(parts[-1])
        self._tail[sid] = int(parts[-1][-1])

    def _close_run(self, sid: int) -> None:
        if not self._cur[sid]:
            return
        run = (
            self._cur[sid][0]
            if len(self._cur[sid]) == 1
            else np.concatenate(self._cur[sid])
        )
        self._cur[sid] = []
        self._tail[sid] = None
        self._run_count[sid] += 1
        if self._metrics is not None:
            self._run_len_buf.append(run.size)
        self._push_run(sid, run, 0)

    def _push_run(self, sid: int, run: np.ndarray, depth: int) -> None:
        levels = self._levels[sid]
        while len(levels) <= depth:
            levels.append([])
        levels[depth].append(run)
        if len(levels[depth]) == self.k:
            with self._tr.span(
                f"ladder:L{depth}", cat="server", tid=self.lane, runs=self.k
            ):
                merged = merge_runs(levels[depth])
            levels[depth] = []
            self._push_run(sid, merged, depth + 1)

    # -- completion -----------------------------------------------------
    def finish(self) -> tuple[np.ndarray, list[int]]:
        """Drain state; return ``(globally sorted stream, passes/segment)``."""
        for sid in range(self.num_segments):
            # A non-empty spilled set means the in-order cursor is still
            # short of a seq whose keys were already fed — i.e. some earlier
            # packet never arrived.  Recovery dedupes and reorders; it never
            # invents keys, so a genuine loss still fails here.
            if self._pending[sid] or self._spilled[sid]:
                have = set(self._pending[sid]) | self._spilled[sid]
                missing = [
                    q
                    for q in range(self._next_seq[sid], max(have) + 1)
                    if q not in have
                ]
                raise ValueError(
                    f"{self.name}: segment {sid}: stream incomplete — "
                    f"missing seqs {_format_seq_ranges(missing)} "
                    f"(next expected {self._next_seq[sid]}, "
                    f"{len(self._pending[sid])} buffered, "
                    f"{len(self._spilled[sid])} spilled out of band)"
                )
        with self._tr.span(
            f"{self.name}:finish", cat="server", tid=self.lane
        ):
            out, passes = self._finish_body()
        if self._metrics is not None:
            if self._run_len_buf:
                self._metrics.histogram(
                    "server_run_length", self.name
                ).observe_many(np.asarray(self._run_len_buf, dtype=np.int64))
                self._run_len_buf = []
            self._metrics.gauge("server_keys_ingested", self.name).set(
                self._ingested
            )
            self._metrics.gauge("server_max_reorder_depth", self.name).set(
                self.max_reorder_depth
            )
            self._metrics.gauge("server_merge_passes", self.name).set(
                list(passes)
            )
            self._metrics.counter("server_runs_detected", self.name).inc(
                sum(
                    a.num_runs for a in self._arenas
                ) if self._arenas is not None else sum(self._run_count)
            )
        return out, passes

    def _finish_body(self) -> tuple[np.ndarray, list[int]]:
        tr = self._tr
        outs: list[np.ndarray] = []
        passes: list[int] = []
        if self._arenas is not None:
            for sid in range(self.num_segments):
                arena = self._arenas[sid]
                if len(arena):
                    starts, lengths = arena.run_offsets()
                    if self._metrics is not None:
                        self._metrics.histogram(
                            "server_run_length", self.name
                        ).observe_many(lengths)
                        self._metrics.gauge(
                            "server_arena_fill", self.name
                        ).high_water(len(arena))
                    with tr.span(
                        f"merge:seg{sid}", cat="server", tid=self.lane,
                        keys=len(arena), runs=int(lengths.size),
                    ):
                        outs.append(
                            merge_runs_flat(
                                arena.keys, starts, lengths,
                                tracer=tr,
                                tid=self.lane,
                            )
                        )
                passes.append(merge_passes(arena.num_runs, self.k))
        else:
            for sid in range(self.num_segments):
                self._close_run(sid)
                remaining = [r for level in self._levels[sid] for r in level]
                if remaining:
                    with tr.span(
                        f"merge:seg{sid}", cat="server", tid=self.lane,
                        runs=len(remaining),
                    ):
                        outs.append(merge_runs(remaining))
                passes.append(merge_passes(self._run_count[sid], self.k))
        if not outs:
            out = np.zeros(0, dtype=np.int64)
        elif self.final_merge:
            with tr.span(
                "merge:final", cat="server", tid=self.lane, runs=len(outs)
            ):
                out = (
                    merge_runs_batched(
                        outs,
                        tracer=tr,
                        tid=self.lane,
                    )
                    if self._arenas is not None
                    else merge_runs(outs)
                )
        else:
            out = np.concatenate(outs)
        assert len(out) == self._ingested
        return out, passes


def _format_seq_ranges(seqs: list[int]) -> str:
    """Compress a sorted seq list into range notation: ``[3-5, 9]`` — the
    loss-diagnostic shape the finish() error reports."""
    if not seqs:
        return "[]"
    parts: list[str] = []
    lo = prev = seqs[0]
    for q in seqs[1:]:
        if q == prev + 1:
            prev = q
            continue
        parts.append(str(lo) if lo == prev else f"{lo}-{prev}")
        lo = prev = q
    parts.append(str(lo) if lo == prev else f"{lo}-{prev}")
    return "[" + ", ".join(parts) + "]"


def stream_sort(
    packets: list[Packet],
    num_segments: int,
    k: int = 10,
    reorder_capacity: int | None = None,
) -> tuple[np.ndarray, list[int]]:
    """One-shot convenience: ingest every packet, then finish."""
    server = StreamingServer(num_segments, k=k, reorder_capacity=reorder_capacity)
    for p in packets:
        server.ingest(p)
    return server.finish()


def plain_runs_upper_bound(values: np.ndarray, k: int) -> int:
    """Passes a switchless server would need on the raw stream (baseline)."""
    return merge_passes(int(run_starts(np.asarray(values)).size), k)
