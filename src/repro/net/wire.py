"""Columnar wire format: the dataplane's struct-of-arrays packet stream.

The per-object :class:`~repro.net.packet.Packet` list is faithful to how a
NIC sees the wire, but it forces every hop into per-packet Python loops —
nothing like the line-rate, full-pipeline parallelism the paper's switch
achieves.  :class:`WireBatch` keeps the *same information* as a struct of
arrays: one row per key, with the packet header fields (``flow_id``,
``seq``, ``segment_id``) replicated down their payload's rows and an
``epoch`` tag for the adaptive control plane's re-partitioning epochs.
Packet boundaries are not stored; they are recovered exactly as the run of
consecutive rows sharing one ``(flow_id, seq, segment_id)`` header (header
tuples are unique per packet: ``seq`` is a per-(flow, segment) counter), so
``from_packets``/``to_packets`` round-trip losslessly and every batched
operator can be checked byte-for-byte against its packet-list twin.

Everything here is O(number of keys) numpy — gathers, repeats, and one
argsort where an interleave demands it — and is the substrate the fused hop
engine (:mod:`repro.net.engine`), the hop-graph scheduler
(:mod:`repro.net.topology`), and the streaming server's batch ingest
(:mod:`repro.net.server`) operate on.
"""

from __future__ import annotations

import dataclasses

import numpy as np

from repro.obs.telemetry import IntColumns

from .packet import DEFAULT_PAYLOAD, UNTAGGED, Packet


def ragged_arange(sizes: np.ndarray) -> np.ndarray:
    """``concatenate([arange(s) for s in sizes])`` without the Python loop."""
    sizes = np.asarray(sizes, dtype=np.int64)
    total = int(sizes.sum())
    if total == 0:
        return np.zeros(0, dtype=np.int64)
    starts = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    return np.arange(total, dtype=np.int64) - np.repeat(starts, sizes)


def ragged_gather(starts: np.ndarray, sizes: np.ndarray) -> np.ndarray:
    """Indices of the slices ``[starts[i], starts[i] + sizes[i])``, in order.

    The columnar workhorse: expanding per-packet (start, size) pairs into
    per-key gather indices is how batched operators move ragged packet
    slices without looping.
    """
    return np.repeat(starts, sizes) + ragged_arange(sizes)


@dataclasses.dataclass(frozen=True, eq=False)  # ndarray fields: generated
class WireBatch:  # __eq__/__hash__ would raise; compare columns explicitly
    """A packet stream as columns; one row per key, wire (arrival) order."""

    values: np.ndarray  # (n,) int64 keys, or wide keys' (n, W) int32 words
    flow_id: np.ndarray  # (n,) originating storage server / emitting hop
    seq: np.ndarray  # (n,) per-(flow, segment) packet sequence number
    segment_id: np.ndarray  # (n,) the paper's port number (UNTAGGED pre-switch)
    epoch: int = 0  # control-plane epoch this batch routes under
    int_meta: IntColumns | None = None  # INT per-hop telemetry stack (opt-in)
    # Payload provenance: original input row of each key, for engines that
    # carry whole records (key + payload columns) through the fabric.  The
    # payload bytes themselves never ride the wire — they are gathered once
    # at egress by indexing the storage-side payload table with this column.
    row_index: np.ndarray | None = None  # (n,) int64, opt-in
    # Owning job of each key (the multi-tenant serving plane's demux key).
    # Carried at ingress and egress; inside the fabric tenancy lives in the
    # per-tenant segment-id blocks instead (P4DB/Cheetah-style per-query
    # switch state), so engines may drop the column mid-fabric.
    tenant: np.ndarray | None = None  # (n,) int64, opt-in

    def __post_init__(self) -> None:
        # Keys wider than 64 bits ride as a matrix of int32 sort words, one
        # row per key (:func:`repro.core.partition.key_words`).
        wide = np.ndim(self.values) == 2
        for name in ("values", "flow_id", "seq", "segment_id"):
            dtype = np.int32 if wide and name == "values" else np.int64
            object.__setattr__(
                self, name, np.asarray(getattr(self, name), dtype=dtype)
            )
        n = len(self.values)
        for name in ("flow_id", "seq", "segment_id"):
            if getattr(self, name).size != n:
                raise ValueError(f"column {name} length != values length {n}")
        if self.int_meta is not None and len(self.int_meta) != n:
            raise ValueError(
                f"int_meta rows {len(self.int_meta)} != values length {n}"
            )
        if self.row_index is not None:
            object.__setattr__(
                self, "row_index", np.asarray(self.row_index, dtype=np.int64)
            )
            if self.row_index.size != n:
                raise ValueError(
                    f"row_index length {self.row_index.size} != values "
                    f"length {n}"
                )
        if self.tenant is not None:
            object.__setattr__(
                self, "tenant", np.asarray(self.tenant, dtype=np.int64)
            )
            if self.tenant.size != n:
                raise ValueError(
                    f"tenant length {self.tenant.size} != values length {n}"
                )

    def __len__(self) -> int:
        return len(self.values)

    # -- packet-boundary view ------------------------------------------
    def packet_starts(self) -> np.ndarray:
        """Start index of every packet (a maximal run of one header)."""
        n = len(self)
        if n == 0:
            return np.zeros(0, dtype=np.int64)
        change = (
            (self.flow_id[1:] != self.flow_id[:-1])
            | (self.seq[1:] != self.seq[:-1])
            | (self.segment_id[1:] != self.segment_id[:-1])
        )
        if self.tenant is not None:
            # Adjacent packets from different jobs may otherwise share a
            # header tuple (e.g. raw storage traffic, all UNTAGGED) and fuse.
            change = change | (self.tenant[1:] != self.tenant[:-1])
        return np.concatenate([[0], np.nonzero(change)[0] + 1]).astype(np.int64)

    def packet_ordinal(self) -> np.ndarray:
        """Per-key 0-based index of the packet the key rides in."""
        n = len(self)
        if n == 0:
            return np.zeros(0, dtype=np.int64)
        starts = self.packet_starts()
        sizes = np.diff(np.concatenate([starts, [n]]))
        return np.repeat(np.arange(starts.size, dtype=np.int64), sizes)

    @property
    def num_packets(self) -> int:
        return int(self.packet_starts().size)

    # -- reshaping ------------------------------------------------------
    def take(self, idx: np.ndarray) -> "WireBatch":
        """Row gather (boolean mask or index array), order-preserving.

        The INT telemetry stack and the payload row-index column follow
        their keys through the same gather.
        """
        return WireBatch(
            self.values[idx],
            self.flow_id[idx],
            self.seq[idx],
            self.segment_id[idx],
            epoch=self.epoch,
            int_meta=None if self.int_meta is None else self.int_meta.take(idx),
            row_index=None if self.row_index is None else self.row_index[idx],
            tenant=None if self.tenant is None else self.tenant[idx],
        )

    def slice_keys(self, lo: int, hi: int) -> "WireBatch":
        return WireBatch(
            self.values[lo:hi],
            self.flow_id[lo:hi],
            self.seq[lo:hi],
            self.segment_id[lo:hi],
            epoch=self.epoch,
            int_meta=(
                None if self.int_meta is None else self.int_meta.slice(lo, hi)
            ),
            row_index=(
                None if self.row_index is None else self.row_index[lo:hi]
            ),
            tenant=None if self.tenant is None else self.tenant[lo:hi],
        )

    def with_epoch(self, epoch: int, num_segments: int) -> "WireBatch":
        """Epoch handoff on columns: shift ports into the epoch's virtual
        segment-id block (the adaptive plane's correctness trick)."""
        return WireBatch(
            self.values,
            self.flow_id,
            self.seq,
            self.segment_id + epoch * num_segments,
            epoch=epoch,
            int_meta=self.int_meta,
            row_index=self.row_index,
            tenant=self.tenant,
        )

    def with_int_meta(self, int_meta: IntColumns | None) -> "WireBatch":
        """The same wire rows carrying a different telemetry stack."""
        return WireBatch(
            self.values,
            self.flow_id,
            self.seq,
            self.segment_id,
            epoch=self.epoch,
            int_meta=int_meta,
            row_index=self.row_index,
            tenant=self.tenant,
        )

    def with_row_index(self, row_index: np.ndarray | None) -> "WireBatch":
        """The same wire rows carrying a (different) payload row column."""
        return WireBatch(
            self.values,
            self.flow_id,
            self.seq,
            self.segment_id,
            epoch=self.epoch,
            int_meta=self.int_meta,
            row_index=row_index,
            tenant=self.tenant,
        )

    def with_tenant(self, tenant) -> "WireBatch":
        """The same wire rows stamped with a tenant column.

        ``tenant`` may be a scalar job id (broadcast down the rows), a
        per-row array, or ``None`` to strip the column.
        """
        if tenant is not None and np.ndim(tenant) == 0:
            tenant = np.full(len(self), int(tenant), dtype=np.int64)
        return WireBatch(
            self.values,
            self.flow_id,
            self.seq,
            self.segment_id,
            epoch=self.epoch,
            int_meta=self.int_meta,
            row_index=self.row_index,
            tenant=tenant,
        )

    # -- Packet interop (the thin boundary view) ------------------------
    @classmethod
    def from_packets(cls, packets: list[Packet], epoch: int = 0) -> "WireBatch":
        if not packets:
            return empty_batch(epoch)
        sizes = [p.size for p in packets]
        tenant = None
        if any(p.tenant_id for p in packets):
            tenant = np.repeat([p.tenant_id for p in packets], sizes)
        return cls(
            np.concatenate([p.payload for p in packets]),
            np.repeat([p.flow_id for p in packets], sizes),
            np.repeat([p.seq for p in packets], sizes),
            np.repeat([p.segment_id for p in packets], sizes),
            epoch=epoch,
            tenant=tenant,
        )

    def to_packets(self) -> list[Packet]:
        n = len(self)
        bounds = np.concatenate([self.packet_starts(), [n]])
        return [
            Packet(
                self.values[a:b],
                int(self.flow_id[a]),
                int(self.seq[a]),
                int(self.segment_id[a]),
                tenant_id=0 if self.tenant is None else int(self.tenant[a]),
            )
            for a, b in zip(bounds[:-1], bounds[1:])
        ]


def empty_batch(epoch: int = 0) -> WireBatch:
    z = np.zeros(0, dtype=np.int64)
    return WireBatch(z, z, z, z, epoch=epoch)


def packetize_batch(
    values: np.ndarray,
    payload_size: int = DEFAULT_PAYLOAD,
    *,
    flow_id: int = 0,
    segment_id: int = UNTAGGED,
    start_seq: int = 0,
) -> WireBatch:
    """Columnar :func:`repro.net.packet.packetize`: chop a key stream into
    fixed-size packets (ragged tail allowed) without materializing them."""
    values = np.asarray(values, dtype=np.int64)
    if payload_size <= 0:
        raise ValueError("payload_size must be positive")
    n = values.size
    seq = start_seq + np.arange(n, dtype=np.int64) // payload_size
    return WireBatch(
        values,
        np.full(n, flow_id, dtype=np.int64),
        seq,
        np.full(n, segment_id, dtype=np.int64),
    )


def concat_batches(batches: list[WireBatch]) -> WireBatch:
    """Concatenate in list order.  The epoch tag survives only if uniform
    (a multi-epoch delivered stream carries its epochs in the virtual
    segment ids instead)."""
    batches = [b for b in batches]
    if not batches:
        return empty_batch()
    epochs = {b.epoch for b in batches}
    # Telemetry survives when every key-carrying part has it (empty parts
    # have nothing to say); a mixed stream degrades to no telemetry.
    carrying = [b for b in batches if len(b)]
    int_meta = None
    if carrying and all(b.int_meta is not None for b in carrying):
        int_meta = IntColumns.concat([b.int_meta for b in carrying])
    row_index = None
    if carrying and all(b.row_index is not None for b in carrying):
        row_index = np.concatenate([b.row_index for b in carrying])
    tenant = None
    if carrying and all(b.tenant is not None for b in carrying):
        tenant = np.concatenate([b.tenant for b in carrying])
    return WireBatch(
        # Empty parts add no keys (and carry no word matrix's width).
        np.concatenate([b.values for b in carrying or batches]),
        np.concatenate([b.flow_id for b in batches]),
        np.concatenate([b.seq for b in batches]),
        np.concatenate([b.segment_id for b in batches]),
        epoch=epochs.pop() if len(epochs) == 1 else 0,
        int_meta=int_meta,
        row_index=row_index,
        tenant=tenant,
    )


def merge_round_robin_batches(streams: list[WireBatch]) -> WireBatch:
    """Columnar :func:`repro.net.packet.merge_round_robin`: one packet per
    stream per turn — vectorized as a stable sort of keys by
    ``(packet ordinal within its stream, stream index)``."""
    streams = [s for s in streams if len(s)]
    if not streams:
        return empty_batch()
    if len(streams) == 1:
        return streams[0]
    turn = np.concatenate([s.packet_ordinal() for s in streams])
    src = np.repeat(np.arange(len(streams), dtype=np.int64),
                    [len(s) for s in streams])
    pos = np.concatenate(
        [np.arange(len(s), dtype=np.int64) for s in streams]
    )
    order = np.lexsort((pos, src, turn))
    cat = concat_batches(streams)
    return cat.take(order)


def split_by_flow(batch: WireBatch, num_groups: int) -> list[WireBatch]:
    """Ingress cabling: storage flow ``f`` feeds group ``f % num_groups``.

    Row-order-preserving masks, so each group's stream is exactly the
    sub-sequence of arrivals the per-packet fan-out would collect.
    """
    if num_groups <= 0:
        raise ValueError("num_groups must be positive")
    group = batch.flow_id % num_groups
    return [batch.take(group == g) for g in range(num_groups)]


def segment_streams_batch(batch: WireBatch, num_segments: int) -> list[np.ndarray]:
    """Columnar :func:`repro.net.packet.segment_streams`: demux keys by port
    number into per-segment streams in arrival order."""
    sids = batch.segment_id
    if sids.size and (sids.min() < 0 or sids.max() >= num_segments):
        bad = int(sids.min()) if sids.min() < 0 else int(sids.max())
        raise ValueError(f"packet with untagged/invalid segment {bad}")
    order = np.argsort(sids, kind="stable")
    counts = (
        np.bincount(sids, minlength=num_segments)
        if sids.size
        else np.zeros(num_segments, dtype=np.int64)
    )
    return np.split(batch.values[order], np.cumsum(counts)[:-1])
