"""Sharded egress: a segment-affinity pool of streaming compute servers.

The paper's scale argument (§5) is that once the switch has installed
contiguous key ranges, the server side can "sort each range separately and
then concatenate" — which means the egress need not be *one* server at all.
:class:`ServerPool` realizes that claim: the fabric's delivered wire batch
is demultiplexed by **segment affinity** — every (virtual) segment id maps
to exactly one of ``S`` independent :class:`~repro.net.server.StreamingServer`
instances, each running the unmodified bounded-reorder / run-detection /
k-way-merge-ladder logic on only its range shard — and a distributed merge
(:func:`repro.core.distributed.pool_concat`) reassembles the global order
from the per-server outputs.

Affinity is *contiguous in key space*: server ``s`` owns a contiguous block
of base segments (:func:`segment_affinity`), so within one control-plane
epoch the per-server outputs hold disjoint ascending key ranges and the
distributed merge is a pure concatenation in server order — exactly the
paper's sentence, sharded.  Under the adaptive control plane's epoched
re-partitioning the virtual segment ids are re-sharded onto the same
affinity blocks (:meth:`repro.net.control.AdaptiveControlPlane.pool_affinity`)
— a server keeps its lane across handoffs, but ranges from different epochs
overlap, so each server k-way merges its own (epoch, segment) outputs
(``final_merge``) and the pool-level merge becomes a k-way merge of the
``S`` sorted server streams.  Either way the output equals
``np.sort(input)`` byte for byte — sharding, like range estimation, can
cost balance but never correctness.

Timing model: the servers are independent machines, so the pool's
wall-clock is the *makespan* — the slowest server's ingest+finish time plus
the distributed merge — even though this process simulates them
sequentially.  Per-server seconds, key counts, and the peak-over-mean key
imbalance are exposed for the ``server_scaling`` benchmark section.  The
demux itself (one mask per server) is the switch egress's port-based
routing and is charged to neither side.
"""

from __future__ import annotations

import numpy as np

from repro.obs.trace import NULL_TRACER

from ..core.distributed import pool_concat
from .server import StreamingServer
from .wire import WireBatch, ragged_gather


def segment_affinity(num_segments: int, num_servers: int) -> np.ndarray:
    """Contiguous-block map from base segment id to owning server.

    ``(num_segments,)`` int64 with server ``b * num_servers // num_segments``
    owning base segment ``b`` — non-decreasing, every server gets a block of
    ``floor(S_seg/S)`` or ``ceil(S_seg/S)`` consecutive segments, so server
    order is key-range order and per-epoch concatenation stays sorted.
    """
    if num_servers <= 0:
        raise ValueError("num_servers must be positive")
    if num_servers > num_segments:
        raise ValueError(
            f"num_servers ({num_servers}) exceeds num_segments "
            f"({num_segments}); a server needs at least one segment"
        )
    base = np.arange(num_segments, dtype=np.int64)
    return base * num_servers // num_segments


class ServerPool:
    """``S`` independent streaming servers behind a segment-affinity demux.

    ``num_segments`` is the *base* (per-epoch) segment count; with
    ``num_epochs > 1`` the pool addresses ``num_segments * num_epochs``
    virtual segment ids, re-sharded per epoch onto the same affinity blocks.
    ``affinity`` optionally dictates the base map (the control plane's
    :meth:`~repro.net.control.AdaptiveControlPlane.pool_affinity` hands the
    tiled virtual map back through this); it must be non-decreasing with
    values in ``[0, num_servers)`` so the disjoint-range concatenation
    stays sorted.

    ``merge_backend`` selects every member server's run-merge engine
    (:data:`repro.net.server.MERGE_BACKENDS`): the eager ``"numpy"`` ladder
    or the device-resident ``"arena"`` tournament — byte-identical
    ``(output, passes)``, different wall-clock.  ``pool_backend`` selects
    the *distributed* merge that reassembles the shard outputs: ``"numpy"``
    (default) or ``"shard_map"`` — per-server shards split over a
    ``("server",)`` mesh of the devices present and concatenated with one
    collective (:func:`repro.core.distributed.pool_concat_sharded`; one
    device per server on a host with ``S`` of them, one device for all on a
    single chip).

    ``recovery`` turns on every member server's loss-recovery mode (seq
    dedup + reorder-overflow spill) — required when the delivered wire is
    the raw egress link of the network timing model
    (:mod:`repro.net.timing`), which carries retransmit duplicates and
    late-beyond-jitter packets.
    """

    def __init__(
        self,
        num_segments: int,
        num_servers: int = 1,
        *,
        num_epochs: int = 1,
        k: int = 10,
        reorder_capacity: int | None = None,
        affinity: np.ndarray | None = None,
        merge_backend: str = "numpy",
        pool_backend: str = "numpy",
        recovery: bool = False,
        crash_schedule=None,
        replay_packets: int | None = None,
        tracer=None,
        metrics=None,
    ) -> None:
        if num_epochs < 1:
            raise ValueError("num_epochs must be >= 1")
        if pool_backend not in ("numpy", "shard_map"):
            raise ValueError(
                f"unknown pool_backend {pool_backend!r}; "
                f"options: numpy, shard_map"
            )
        base = segment_affinity(num_segments, num_servers)
        if affinity is not None:
            affinity = np.asarray(affinity, dtype=np.int64)
            want = np.tile(base, num_epochs)
            if affinity.shape != want.shape:
                raise ValueError(
                    f"affinity length {affinity.size} != "
                    f"{num_segments} segments x {num_epochs} epochs"
                )
            if affinity.size and (
                affinity.min() < 0
                or affinity.max() >= num_servers
                or np.any(np.diff(affinity.reshape(num_epochs, -1), axis=1) < 0)
            ):
                raise ValueError(
                    "affinity must be non-decreasing within each epoch with "
                    "values in [0, num_servers) — contiguous key-range "
                    "blocks are what make server-order concatenation sorted"
                )
            self._affinity = affinity
        else:
            self._affinity = np.tile(base, num_epochs)
        self.num_segments = num_segments
        self.num_servers = num_servers
        self.num_epochs = num_epochs
        self.eff_segments = num_segments * num_epochs
        self.recovery = recovery
        self.merge_backend = merge_backend
        self.pool_backend = pool_backend
        # Local segment numbering: server s's virtual segments, ascending,
        # get local ids 0..count-1 — per epoch that is the base-block order,
        # so a server's own concatenation is ascending in key space too.
        counts = np.bincount(self._affinity, minlength=num_servers)
        local = np.zeros(self.eff_segments, dtype=np.int64)
        for s in range(num_servers):
            local[self._affinity == s] = np.arange(counts[s])
        self._local_of = local
        self._tr = tracer or NULL_TRACER
        self._metrics = metrics
        # Each member server traces on its own lane (Chrome tid 1+s) so the
        # pool's simulated-parallel drain renders as parallel tracks.
        self.servers = [
            StreamingServer(
                int(counts[s]) if counts[s] else 1,  # idle server: 1 port
                k=k,
                reorder_capacity=reorder_capacity,
                final_merge=num_epochs > 1,
                merge_backend=merge_backend,
                recovery=recovery,
                tracer=tracer,
                metrics=metrics,
                name=f"server{s}",
                lane=1 + s,
            )
            for s in range(num_servers)
        ]
        self.per_server_seconds = [0.0] * num_servers
        self.merge_seconds = 0.0
        # -- shard-failover state (the fault plane's server_crash path) --
        # ``crash_schedule`` is [(server, at_packets)]: shard s dies after
        # the pool has ingested ``at_packets`` packets (any still pending
        # at finish() fire then).  While a shard has a pending crash, its
        # ingested sub-batches are retained in a bounded replay buffer so
        # the adopting neighbor can rebuild its state.
        self._crash_at: dict[int, int] = {}
        for s, at in crash_schedule or []:
            s = int(s)
            if not 0 <= s < num_servers:
                raise ValueError(
                    f"crash_schedule names server {s}; pool has "
                    f"{num_servers}"
                )
            if num_servers == 1:
                raise ValueError(
                    "cannot schedule a crash on a single-server pool — "
                    "there is no shard to fail over to"
                )
            self._crash_at[s] = int(at)
        self._replay_cap = replay_packets
        self._replay: dict[int, list[WireBatch]] = {
            s: [] for s in self._crash_at
        }
        self._replay_len: dict[int, int] = {s: 0 for s in self._crash_at}
        self._replay_lost: dict[int, int] = {s: 0 for s in self._crash_at}
        self._dead: set[int] = set()
        self._packets_seen = 0
        self.servers_failed_over = 0

    # -- ingestion ------------------------------------------------------
    def ingest_batch(self, batch: WireBatch) -> None:
        """Demux a delivered wire batch by segment affinity; feed each
        server its shard with segment ids renumbered into its local space.

        The demux is packet-granular: masking rows is order-preserving and
        packets are header-contiguous, so every server sees exactly the
        sub-sequence of the wire its NIC would have received — per-segment
        seq order, and therefore the reorder-buffer and run-detection
        behaviour, are unchanged.  In recovery mode, a retransmit copy on
        the raw wire separated from its original only by *other servers'*
        packets would land adjacent to it after the strip and fuse into one
        double-length packet (boundaries are header runs), hiding the
        duplicate from seq dedup — the demux applies the egress link's
        coalescing rule first: adjacent identical copies deliver once.
        """
        if len(batch) == 0:
            return
        sids = batch.segment_id
        if sids.min() < 0 or sids.max() >= self.eff_segments:
            bad = int(sids.min()) if sids.min() < 0 else int(sids.max())
            raise ValueError(f"packet with invalid segment id {bad}")
        if self.num_servers == 1 and not self._crash_at:
            with self._tr.timed("server0:wall", cat="egress", tid=1) as t:
                self.servers[0].ingest_batch(batch)
            self.per_server_seconds[0] += t.seconds
            return
        starts = batch.packet_starts()
        sizes = np.diff(np.concatenate([starts, [len(batch)]]))
        P = int(starts.size)
        # Shard crashes trigger at global packet ordinals: split this
        # batch's packet window at every pending cut, failing the shard
        # over *between* the chunks so packets before the cut land on the
        # dying shard and packets after it follow the updated affinity.
        cuts = sorted(
            (max(at - self._packets_seen, 0), s)
            for s, at in self._crash_at.items()
            if s not in self._dead and at < self._packets_seen + P
        )
        lo = 0
        for cut, s in cuts:
            cut = max(cut, lo)
            if cut > lo:
                self._ingest_packets(batch, starts, sizes, lo, cut)
            self._crash(s)
            lo = cut
        if lo < P:
            self._ingest_packets(batch, starts, sizes, lo, P)
        self._packets_seen += P

    def _ingest_packets(
        self,
        batch: WireBatch,
        starts: np.ndarray,
        sizes: np.ndarray,
        plo: int,
        phi: int,
    ) -> None:
        """Demux one contiguous packet window ``[plo, phi)`` of ``batch``."""
        pflow = batch.flow_id[starts]
        pseq = batch.seq[starts]
        pseg = batch.segment_id[starts]
        pserv = self._affinity[pseg]
        window = np.arange(plo, phi, dtype=np.int64)
        for s in range(self.num_servers):
            sel = window[pserv[window] == s]
            if not sel.size:
                continue
            if self.recovery and sel.size > 1:
                dup = (
                    (pflow[sel][1:] == pflow[sel][:-1])
                    & (pseq[sel][1:] == pseq[sel][:-1])
                    & (pseg[sel][1:] == pseg[sel][:-1])
                )
                if dup.any():
                    keep = np.ones(sel.size, dtype=bool)
                    keep[1:] = ~dup
                    self.servers[s].dup_packets_dropped += int(dup.sum())
                    sel = sel[keep]
            sub = batch.take(ragged_gather(starts[sel], sizes[sel]))
            if s in self._crash_at and s not in self._dead:
                # Retain the shard's history (virtual segment ids — local
                # renumbering changes at failover) for replay, up to the
                # bounded buffer; anything beyond the bound is lost and
                # makes a later crash unrecoverable (checked loudly there).
                self._retain_replay(s, sub)
            sub = WireBatch(
                sub.values,
                sub.flow_id,
                sub.seq,
                self._local_of[sub.segment_id],
                epoch=sub.epoch,
                int_meta=sub.int_meta,
            )
            with self._tr.timed(
                f"server{s}:wall", cat="egress", tid=1 + s
            ) as t:
                self.servers[s].ingest_batch(sub)
            self.per_server_seconds[s] += t.seconds

    def _retain_replay(self, s: int, sub: WireBatch) -> None:
        """Append ``sub`` (packet-contiguous, virtual segment ids) to shard
        ``s``'s bounded replay buffer.  Packets beyond the cap are counted
        as lost — that shard's crash then refuses the failover loudly
        rather than rebuilding a partial (key-destroying) history."""
        starts = sub.packet_starts()
        n = int(starts.size)
        if self._replay_cap is not None:
            room = max(self._replay_cap - self._replay_len[s], 0)
            if n > room:
                self._replay_lost[s] += n - room
                if not room:
                    return
                sizes = np.diff(np.concatenate([starts, [len(sub)]]))
                sub = sub.take(
                    ragged_gather(starts[:room], sizes[:room])
                )
                n = room
        self._replay[s].append(sub)
        self._replay_len[s] += n

    def _crash(self, s: int) -> None:
        """Kill shard ``s``; the nearest alive neighbor adopts its segment
        range and re-ingests its history from the replay buffer.

        Replay in original ingestion order rebuilds the dead shard's
        per-segment state exactly (run detection and the merge ladder are
        order-deterministic), so the pool's final output stays
        byte-identical to the fault-free run — the cost is the adopter's
        extra merge work and a k-way (no longer disjoint) pool merge.
        """
        if s in self._dead:
            return
        alive = [
            t
            for t in range(self.num_servers)
            if t != s and t not in self._dead
        ]
        if not alive:
            raise ValueError(
                f"server{s} crashed with no alive server left to adopt "
                f"its shard — an unsurvivable fault plan"
            )
        if self._replay_lost.get(s, 0):
            raise ValueError(
                f"server{s} crashed but its replay buffer (capacity "
                f"{self._replay_cap} packets) had dropped "
                f"{self._replay_lost[s]} packets — shard unrecoverable; "
                f"raise replay_packets"
            )
        t = min(alive, key=lambda a: (abs(a - s), a))
        self._dead.add(s)
        self.servers_failed_over += 1
        vsegs = np.flatnonzero(self._affinity == s)
        self._tr.instant(
            f"fault:server{s}", cat="fault",
            packets_seen=self._packets_seen,
            virtual_segments=[int(v) for v in vsegs],
        )
        self._tr.instant(f"reroute:server{s}->server{t}", cat="fault")
        if self._metrics is not None:
            self._metrics.counter("pool_failovers", f"server{s}").inc()
        if vsegs.size:
            # Adopted segments get fresh ports appended after the
            # adopter's own; its per-segment outputs are no longer one
            # ascending key range, so it must k-way merge at finish.
            base = self.servers[t].num_segments
            self.servers[t].grow(int(vsegs.size))
            self._local_of[vsegs] = base + np.arange(
                vsegs.size, dtype=np.int64
            )
            self._affinity[vsegs] = t
            self.servers[t].final_merge = True
        history = self._replay.pop(s, [])
        self._replay_len.pop(s, None)
        self._crash_at.pop(s, None)
        # Cascade hazard: if the adopter is itself scheduled to crash, the
        # victim's replayed history becomes part of the adopter's own state
        # — retain it in the adopter's replay buffer (toward its cap) so a
        # second failover can rebuild the first victim's segments too.
        adopter_doomed = t in self._crash_at
        for sub in history:
            if adopter_doomed:
                self._retain_replay(t, sub)
            sub = WireBatch(
                sub.values,
                sub.flow_id,
                sub.seq,
                self._local_of[sub.segment_id],
                epoch=sub.epoch,
                int_meta=sub.int_meta,
            )
            with self._tr.timed(
                f"server{t}:wall", cat="egress", tid=1 + t
            ) as tt:
                self.servers[t].ingest_batch(sub)
            self.per_server_seconds[t] += tt.seconds

    def ingest_grouped(
        self,
        values: np.ndarray,
        seg_counts: np.ndarray,
        run_flags: np.ndarray,
    ) -> None:
        """Segment-grouped handoff from the compiled-epoch dataplane.

        ``values`` holds every segment's complete emission-order stream
        contiguously (segment-ascending — the device program's grouped
        layout), ``seg_counts`` the per-virtual-segment key counts, and
        ``run_flags`` marks maximal-ascending-run starts within the grouped
        stream (the device already computed them for the hop statistics).
        Each server receives its segments as whole in-order streams via
        :meth:`StreamingServer.ingest_segment` — byte-identical to demuxing
        and re-assembling the equivalent packet wire, without touching
        packet headers.  Single-epoch pools only: the multi-epoch handoff
        interleaves epochs on the wire, which this layout cannot express.
        """
        if self.num_epochs != 1:
            raise ValueError(
                "grouped handoff supports single-epoch pools only"
            )
        values = np.asarray(values)
        if len(values) == 0:
            return
        seg_counts = np.asarray(seg_counts, dtype=np.int64)
        if seg_counts.size != self.eff_segments:
            raise ValueError(
                f"seg_counts length {seg_counts.size} != "
                f"{self.eff_segments} segments"
            )
        if int(seg_counts.sum()) != len(values):
            raise ValueError("seg_counts do not sum to the stream length")
        bounds = np.concatenate([[0], np.cumsum(seg_counts)])
        flags = np.asarray(run_flags, dtype=bool)
        for v in range(self.eff_segments):
            a, b = int(bounds[v]), int(bounds[v + 1])
            if a == b:
                continue
            s = int(self._affinity[v])
            starts = np.flatnonzero(flags[a:b]).astype(np.int64)
            with self._tr.timed(
                f"server{s}:wall", cat="egress", tid=1 + s
            ) as t:
                self.servers[s].ingest_segment(
                    int(self._local_of[v]), values[a:b], starts
                )
            self.per_server_seconds[s] += t.seconds

    # -- completion -----------------------------------------------------
    def finish(self) -> tuple[np.ndarray, list[int]]:
        """Drain every server; distributed-merge the shard outputs.

        Returns the same ``(globally sorted stream, passes per virtual
        segment)`` contract as a single :class:`StreamingServer` — passes
        are reassembled into virtual-segment order, so the result is
        byte-identical to the unsharded pipeline's.
        """
        # Crashes scheduled past the end of the stream (or on a stream
        # short enough never to reach the cut) still fire before drain, so
        # the fault plan's failovers always happen.
        for at, s in sorted(
            (at, s) for s, at in self._crash_at.items() if s not in self._dead
        ):
            self._crash(s)
        outs: list[np.ndarray] = []
        per_server_passes: list[list[int]] = []
        for s, server in enumerate(self.servers):
            if s in self._dead:
                outs.append(np.zeros(0, dtype=np.int64))
                per_server_passes.append([])
                continue
            try:
                with self._tr.timed(
                    f"server{s}:wall", cat="egress", tid=1 + s
                ) as t:
                    out, passes = server.finish()
            except ValueError as e:
                owned = np.flatnonzero(self._affinity == s)
                raise ValueError(
                    f"server{s} (virtual segments {owned.tolist()}): {e}"
                ) from e
            self.per_server_seconds[s] += t.seconds
            outs.append(out)
            per_server_passes.append(passes)
        passes = [
            per_server_passes[int(self._affinity[v])][int(self._local_of[v])]
            for v in range(self.eff_segments)
        ]
        with self._tr.timed(
            "pool:merge", cat="egress", servers=self.num_servers
        ) as t:
            output = pool_concat(
                outs,
                disjoint=self.num_epochs == 1 and not self._dead,
                backend=self.pool_backend,
            )
        self.merge_seconds = t.seconds
        if self._metrics is not None:
            self._metrics.gauge("pool_server_keys").set(self.server_keys)
            self._metrics.gauge("pool_imbalance").set(self.server_imbalance)
        return output, passes

    # -- observability --------------------------------------------------
    @property
    def max_reorder_depth(self) -> int:
        """Worst reorder-buffer occupancy across the pool (0 when the pool
        is degenerate — no servers constructed yet)."""
        return max((s.max_reorder_depth for s in self.servers), default=0)

    @property
    def dup_packets_dropped(self) -> int:
        """Retransmit duplicates deduped across the pool (recovery mode)."""
        return sum(s.dup_packets_dropped for s in self.servers)

    @property
    def spilled_packets(self) -> int:
        """Packets fed out of band on reorder overflow, pool-wide."""
        return sum(s.spilled_packets for s in self.servers)

    @property
    def spilled_keys(self) -> int:
        """Keys carried by spilled packets, pool-wide."""
        return sum(s.spilled_keys for s in self.servers)

    @property
    def server_keys(self) -> list[int]:
        """Keys ingested per server (the pool's load distribution).
        Dead shards report 0 — their load moved to the adopter."""
        return [
            0 if s in self._dead else srv.keys_ingested
            for s, srv in enumerate(self.servers)
        ]

    @property
    def server_imbalance(self) -> float:
        """Peak-over-mean per-server key load; 1.0 is a perfect shard
        (also reported for an empty or degenerate pool).

        The mean is taken over servers that *own* at least one segment in
        the affinity map — dividing by ``num_servers`` would deflate the
        figure whenever an (epoch-sliced) affinity leaves servers idle."""
        keys = self.server_keys
        total = sum(keys)
        owners = int(np.unique(self._affinity).size) if total else 0
        if total == 0 or not owners:
            return 1.0
        return max(keys) / (total / owners)

    @property
    def makespan_seconds(self) -> float:
        """The pool's wall-clock: slowest server + distributed merge
        (just the merge for a degenerate pool with no servers)."""
        return max(self.per_server_seconds, default=0.0) + self.merge_seconds
