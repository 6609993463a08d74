"""Whole-topology device residency: one compiled program per fabric epoch.

The per-hop engines (:mod:`repro.net.engine`) realise the paper's line-rate
claim one switch at a time, but the simulator still pays a host round-trip
between every hop: route, rank, sort, packetize, materialize numpy columns,
hand them to the next hop.  Related work (Cheetah; "Programmable Switch as a
Parallel Computing Device") treats the *fabric* as one pipelined computing
device — the jax_pallas analogue is this module: ``engine="device"`` lowers
an entire :class:`~repro.net.topology.HopGraph` epoch — route → rank →
padded segment block-sort → emission order → ship-order packetization at
every hop, leaf→spine→egress in topological order, round-robin uplink
merges included — into **one** jitted program with donated buffers.  Keys
(and, in record mode, their payload row indices) enter the device once at
ingest and leave once at egress; the transfer counters below prove it.

Stage math (per hop, all static-shape jnp over ``n`` arrival keys):

* route: ``searchsorted`` over the shared range bounds (the parse cascade);
* rank: one stable (packed key-only) sort by segment — the grouping
  permutation; per-segment starts and counts are searches into the sorted
  segment column, and each slot's in-segment position follows from them;
* block sort: every segment's L-blocks laid out as rows of one padded
  ``(n//L + S, L)`` matrix.  Each segment's slots move there, and back,
  by one per-segment shift (static slices selected by segment, or one
  gather with a dense index when there are many segments), and every row's
  length is known in closed form.  Bare keys sort with ``jnp.sort`` — or
  with the Pallas bitonic kernel (:func:`repro.kernels.ops.sort_rows_padded`)
  under the same fallback rules as the per-hop fused path; record mode uses
  a stable row argsort so each key's payload row follows it through the
  sort;
* emission order: the slot→emission-index map.  Only a segment's first L
  arrivals do not emit on arrival, so an arrival's emission ordinal is its
  index less the number of those before it (one search), and slot ``p`` is
  emitted by the arrival at slot ``p + L``: a static shift by L.  The
  flush tails follow in segment-major order — exactly Alg. 3's two flush
  passes.  No stage of the hop scatters (``.at[]`` or ``bincount``); each
  traced hop says so in :data:`repro.kernels.ops.LOWERING_COUNTS`;
* wire order: a packet ships when its last key is emitted, vectorized as a
  stable argsort of per-key ship indices (all keys of a packet share their
  packet's ship index, so the stable sort reproduces the fused engine's
  packet-granular permutation byte for byte);
* uplink merge: the fair round-robin interleave is a stable argsort of
  per-key packet ordinals over the parents' concatenated outputs.

Byte-identity with the ``fused``/``segment``/``faithful`` engines — wire
columns, HopStats scalars, and server pass counts — is pinned by
``tests/test_device_epoch.py`` across the scenario × topology × range-mode
matrix.

Observability: a tracer never changes the program.  The host side of an
epoch runs in the spans ``epoch:build`` (a program built on a cache
miss), ``epoch:put``, ``epoch:device`` (dispatch until the result is
ready) and ``epoch:fetch``; inside the program, ``jax.named_scope`` names
each hop (``hop_<name>``), its stages (``route``, ``rank``,
``block_sort``, ``emit``, ``ship``) and the uplink merge
(``uplink_merge``), so a profiler trace attributes every device operation
to a stage.  With no metrics registry or network attached the program
returns only the egress columns + per-hop stat scalars (one fetch).  When
``metrics``/``network`` observe the run, the *same single fetch*
additionally carries every hop's output columns and per-key ship indices;
the host then replays the bookkeeping — metrics counters and the
:class:`~repro.net.timing.GraphTimer` emission cuts — over reconstructed
:class:`~repro.net.wire.WireBatch` objects, so the timing overlay sees
exactly what the per-hop loop would have shown it.

Wide keys (9 to 16 bytes, :func:`repro.net.pipeline.run_pipeline`) build
their own program from a wide range table: each key rides as ``W`` int32
sort words with its input row as the last sort word, route counts the
range bounds at or below the key lexicographically, the block sort is the
multi-word Pallas kernel, ship gathers every word column, and run breaks
compare the word tuples (:func:`_device_hop_wide`).  Rank and emit are the
one-word hop's own code.

The egress result is a :class:`DeviceDelivery`: a wire batch that also
carries the segment-grouped emission streams and their run-break flags, so
the server pool can feed each segment's run arena directly
(:meth:`repro.net.egress.ServerPool.ingest_grouped`) without re-deriving
packet boundaries or re-detecting runs on the host.
"""

from __future__ import annotations

import dataclasses
import warnings

import numpy as np

from repro.obs.trace import NULL_TRACER
from repro.transfers import (  # noqa: F401  (TRANSFER_COUNTS: re-exported)
    TRANSFER_COUNTS,
    fetch,
    nbytes,
    reset_transfer_counts,
    to_device,
)

from .engine import HopSpec, HopStats
from .wire import (
    WireBatch,
    empty_batch,
    merge_round_robin_batches,
    split_by_flow,
)

#: Test/CI hook: force the Pallas block-sort kernel's interpret mode
#: (None = the platform default chosen by :mod:`repro.kernels.ops`).
KERNEL_INTERPRET: bool | None = None

_PROGRAM_CACHE: dict = {}
_PROGRAM_CACHE_MAX = 64


@dataclasses.dataclass(frozen=True, eq=False)
class DeviceDelivery(WireBatch):
    """The device epoch's egress wire batch plus its grouped handoff view.

    ``grouped_values`` is the egress hop's emitted stream grouped by
    segment (each segment's slice is its emission-order stream — exactly
    the order the server's reorder buffer would restore), ``seg_counts``
    the per-segment key counts, and ``run_flags`` the maximal-ascending-run
    start flags the device already computed for the hop statistics.  Any
    row gather (``take``/``slice_keys``/jitter) degrades to a plain
    :class:`WireBatch`, which makes the pool's fast-path condition a simple
    ``isinstance``-free ``getattr`` check.  For wide keys ``values`` is the
    ``(n, W)`` sort-word matrix and ``grouped_values`` the ``(n, W + 1)``
    words with each key's input row as the last column.
    """

    grouped_values: np.ndarray | None = None
    grouped_rows: np.ndarray | None = None
    seg_counts: np.ndarray | None = None
    run_flags: np.ndarray | None = None


# ---------------------------------------------------------------------------
# Traced per-hop math
# ---------------------------------------------------------------------------


def _stable_perm(key, n: int):
    """Permutation of ``jnp.argsort(key, stable=True)`` via one *key-only*
    sort of ``(key << ibits) | index``.

    The packed index is unique, so the plain sort's tie order equals the
    stable argsort's arrival order exactly — but a monolithic-key sort is
    several times faster than a variadic key+payload sort on the CPU/TPU
    sort lowering, and this permutation is the hot operation of every hop
    stage.  Requires non-negative keys and ``bits(key) + bits(n)`` ≤ 63,
    which the program builder guarantees before choosing this path.
    """
    import jax.numpy as jnp

    i64 = jnp.int64
    ibits = max(1, (n - 1).bit_length()) if n > 1 else 1
    packed = jnp.sort(
        (key.astype(i64) << ibits) | jnp.arange(n, dtype=i64)
    )
    return packed & ((1 << ibits) - 1), packed >> ibits


#: Largest segment count whose block layout moves by per-segment static
#: slices; past it one gather with a dense index does the same move.  The
#: slices read the stream once per segment, the gather pays a fixed price;
#: the slices' compile time grows with S (TPU v5e timings: PERF.md,
#: Findings).
_SLICED_SEGMENTS_MAX = 256

#: Largest table of first arrivals (S*L) that the emission search compares
#: every arrival with; past it the search sorts (TPU v5e timings:
#: PERF.md, Findings).
_COMPARE_ALL_MAX = 1 << 13


def _index_dtype(size: int):
    """int32 where every index below ``size`` fits, else int64."""
    import jax.numpy as jnp

    return jnp.int32 if size < 1 << 31 else jnp.int64


class _BlockRows:
    """Every segment's L-blocks as the rows of one padded ``(R, L)`` matrix,
    ``R = n // L + S``, and the moves between it and the grouped layout.

    Grouped slot ``p`` of segment ``s`` (in-segment position ``q``) sits at
    cell ``(blk_base[s] + q // L, q % L)``: flat cell ``p + d[s]`` with the
    per-segment shift ``d = blk_base * L - starts``.  Both moves are copies
    by that shift (``S`` static slices selected by segment, or one gather
    for many segments), and each row's segment, start and length follow in
    closed form from ``cumsum(nblk)``.
    """

    def __init__(self, seg_g, counts, starts, S: int, L: int):
        import jax.numpy as jnp

        i64 = jnp.int64
        n = int(seg_g.shape[0])
        self.n, self.S, self.L, self.R = n, S, L, n // L + S
        self.idx = _index_dtype(self.R * L)
        self.seg_g = seg_g
        nblk = -(-counts // L)
        cum_blk = jnp.cumsum(nblk)
        blk_base = cum_blk - nblk
        # Row r holds segment s_row[r]; rows past the used ones get S, whose
        # table entries below are 0 (an empty segment).
        r = jnp.arange(self.R, dtype=i64)
        s_row = jnp.searchsorted(cum_blk, r, side="right")
        pad0 = jnp.zeros(1, i64)
        self.base, self.starts, self.counts = blk_base * L, starts, counts
        self.d = self.base - starts
        self.row_start = r * L - jnp.concatenate([self.d, pad0])[s_row]
        self.row_len = jnp.clip(
            jnp.concatenate([counts, pad0])[s_row]
            - (r - jnp.concatenate([blk_base, pad0])[s_row]) * L,
            0, L,
        )
        self.cols = jnp.arange(L, dtype=i64)[None, :]
        self.valid = self.cols < self.row_len[:, None]

    def slots(self):
        """Each cell's grouped slot (``n`` on pad cells)."""
        import jax.numpy as jnp

        return jnp.where(
            self.valid, self.row_start[:, None] + self.cols, self.n
        )

    def to_rows(self, x, pad):
        """The grouped column ``x`` laid out as the ``(R, L)`` matrix, pad
        cells holding ``pad``."""
        import jax.numpy as jnp
        from jax import lax

        n, S, L, R = self.n, self.S, self.L, self.R
        if not n:  # a hop with no arrivals (an ingress group with no flow)
            return jnp.full((R, L), pad, x.dtype)
        if S > _SLICED_SEGMENTS_MAX:
            src = jnp.clip(self.row_start[:, None] + self.cols, 0, n - 1)
            return jnp.where(self.valid, x[src], pad)
        # Cell t of segment s holds x[t - d[s]]: a slice of x front-padded
        # by the largest shift, (S - 1) * (L - 1).  Segments start at cell
        # base[s] in order, so the last one started by t owns it (an empty
        # segment is overridden by the next), and t is valid below that
        # segment's base + count.  All flat, so the selects fuse into one
        # pass; the matrix shape comes once, at the end.
        D = (S - 1) * (L - 1)
        xp = lax.pad(x, jnp.asarray(pad, x.dtype), [(D, R * L - n, 0)])
        t = jnp.arange(R * L, dtype=self.idx)
        base = self.base.astype(self.idx)
        end = (self.base + self.counts).astype(self.idx)
        out = jnp.full(R * L, pad, x.dtype)
        lim = jnp.zeros(R * L, self.idx)
        for s in range(S):
            inside = t >= base[s]
            sl = lax.dynamic_slice(xp, (D - self.d[s],), (R * L,))
            out = jnp.where(inside, sl, out)
            lim = jnp.where(inside, end[s], lim)
        return jnp.where(t < lim, out, pad).reshape(R, L)

    def from_rows(self, mat):
        """The matrix's valid cells back in grouped layout:
        ``out[p] = flat[p + d[seg_g[p]]]``."""
        import jax.numpy as jnp
        from jax import lax

        n = self.n
        flat = mat.reshape(-1)
        if self.S > _SLICED_SEGMENTS_MAX:
            return flat[jnp.arange(n, dtype=self.d.dtype) + self.d[self.seg_g]]
        # Slot p belongs to the last segment starting at or before it.
        p = jnp.arange(n, dtype=self.idx)
        starts = self.starts.astype(self.idx)
        out = flat[:n]  # d[0] = 0
        for s in range(1, self.S):
            sl = lax.dynamic_slice(flat, (self.d[s],), (n,))
            out = jnp.where(p >= starts[s], sl, out)
        return out


def _emit_index(order, seg_g, q, counts, starts, L: int):
    """Each grouped slot's emission index (Alg. 3).

    Arrivals past a segment's first L emit on arrival, in arrival order;
    then every segment's last ``min(count, L)`` slots flush, segment-major.
    Only the first L arrivals of each segment do not emit on arrival, so an
    arrival's emission ordinal is its index less the number of those
    arrivals up to it, and the arrival with in-segment rank ``q + L``
    (grouped slot ``p + L``) is the one that emitted slot ``p``: a static
    shift by L.
    """
    import jax.numpy as jnp

    i64 = jnp.int64
    n = int(order.shape[0])
    if not n:  # a hop with no arrivals (an ingress group with no flow)
        return jnp.zeros(0, i64)
    n_emitted = jnp.maximum(counts - L, 0)
    flush_mask = q >= n_emitted[seg_g]
    flush_ord = n_emitted.sum() + jnp.cumsum(flush_mask).astype(i64) - 1
    j = jnp.arange(L, dtype=i64)[None, :]
    first = jnp.where(
        j < counts[:, None],
        order[jnp.clip(starts[:, None] + j, 0, n - 1)],
        n,
    )
    # The search compares each arrival with all S*L first arrivals where
    # that table is small, in 32 bits where n fits.
    idx = _index_dtype(n + 1)
    oi = order.astype(idx)
    method = "compare_all" if first.size <= _COMPARE_ALL_MAX else "sort"
    emit_ord_g = oi - jnp.searchsorted(
        jnp.sort(first.reshape(-1).astype(idx)), oi, side="right",
        method=method,
    ).astype(idx)
    shifted = jnp.concatenate(
        [emit_ord_g[L:], jnp.zeros(min(L, n), idx)]
    ).astype(i64)
    return jnp.where(flush_mask, flush_ord, shifted)


def _device_hop(vals, rows, bounds, S: int, L: int, P: int,
                vbits: int, use_kernel: bool, interpret: bool | None):
    """One hop, traced: returns the hop's wire columns + stat scalars.

    ``vals``/``rows`` are the arrival stream (rows is None outside record
    mode); every shape is static, so the whole epoch lowers to one XLA
    program.  The math mirrors :func:`repro.core.marathon.marathon_emission`
    + :func:`repro.net.engine._wire_from_grouped` exactly — see the module
    docstring for the correspondence proof obligations.

    ``vbits`` is the key domain's bit width (0 when packed sorts are
    infeasible — huge domains fall back to stable argsorts, byte-identical
    but slower).  No stage scatters: each traced hop counts
    ``epoch_hop:scatter_free:int64`` in
    :data:`repro.kernels.ops.LOWERING_COUNTS`.

    Each stage runs in a ``jax.named_scope`` of its name (``route``,
    ``rank``, ``block_sort``, ``emit``, ``ship``), which names its
    operations in a profiler trace and changes nothing else.
    """
    import jax
    import jax.numpy as jnp

    from ..kernels import ops

    if isinstance(vals, tuple):
        return _device_hop_wide(
            vals, rows, bounds, S, L, P, vbits, use_kernel, interpret
        )
    i64 = jnp.int64
    n = int(vals.shape[0])
    packable = vbits > 0
    ops.LOWERING_COUNTS["epoch_hop:scatter_free:int64"] += 1
    with jax.named_scope("route"):
        seg = jnp.searchsorted(bounds, vals, side="right").astype(i64)
    with jax.named_scope("rank"):
        order, seg_g, starts, counts, q = _rank(seg, n, S, packable)
        grouped = vals[order]

    # -- block sort: rows of one padded (R, L) matrix -------------------
    with jax.named_scope("block_sort"):
        blocks = _BlockRows(seg_g, counts, starts, S, L)
        cbits = max(1, (L - 1).bit_length())
        if rows is not None and packable and vbits + cbits <= 63:
            # Record mode, packed: each cell carries ``(value << cbits) |
            # col`` so one key-only row sort both orders the values and
            # tells every key which grouped slot it came from
            # (``row_start[row] + col``) — the provenance gather that routes
            # payload rows.  Pad cells keep the all-ones value with their
            # own column in the low bits: they sort after every real key
            # (ties with a real max-valued key break toward the real key's
            # smaller column — the same stable tie-break as the fused
            # engine's provenance lexsort) and never return to a slot.
            pad_val = (1 << vbits) - 1
            cmask = (1 << cbits) - 1
            pk = blocks.to_rows(grouped << cbits, pad_val << cbits)
            pk = pk | blocks.cols
            spk = jnp.sort(pk, axis=1)
            src = jnp.clip(
                blocks.row_start[:, None] + (spk & cmask), 0, max(n - 1, 0)
            )
            stream = blocks.from_rows(spk >> cbits)
            stream_rows = rows[order][blocks.from_rows(src)]
        elif rows is not None:
            # Record mode, wide keys: a stable row argsort keeps the
            # within-block arrival order on ties — the same tie-break as
            # the fused engine's provenance lexsort — so payload rows
            # follow their keys exactly.
            mat = blocks.to_rows(grouped, jnp.iinfo(i64).max)
            perm = jnp.argsort(mat, axis=1, stable=True)
            sorted_vals = jnp.take_along_axis(mat, perm, axis=1)
            sorted_pos = jnp.take_along_axis(blocks.slots(), perm, axis=1)
            stream = blocks.from_rows(sorted_vals)
            stream_rows = rows[order][blocks.from_rows(sorted_pos)]
        elif use_kernel:
            pad32 = jnp.iinfo(jnp.int32).max
            mat32 = blocks.to_rows(grouped.astype(jnp.int32), pad32)
            sorted32 = ops.sort_rows_padded(mat32, interpret=interpret)
            stream = blocks.from_rows(sorted32).astype(i64)
            stream_rows = None
        else:
            mat = blocks.to_rows(grouped, jnp.iinfo(i64).max)
            stream = blocks.from_rows(jnp.sort(mat, axis=1))
            stream_rows = None

    # -- emission order: slot → emission index --------------------------
    with jax.named_scope("emit"):
        eidx = _emit_index(order, seg_g, q, counts, starts, L)

    # -- wire order: packets ship at their last key's emission ----------
    with jax.named_scope("ship"):
        out_perm, ship_key, pkt_j = _ship_order(
            eidx, seg_g, q, counts, starts, n, P, packable
        )
        vals_out = stream[out_perm]
        sid_out = seg_g[out_perm]
        seq_out = pkt_j[out_perm]

        turn, brk = _turns_and_breaks(
            seq_out, sid_out, seg_g, lambda: stream[1:] < stream[:-1], n
        )

        hop = {
            "vals": vals_out,
            "seq": seq_out,
            "sid": sid_out,
            "turn": turn,
            "ship": ship_key[out_perm],
            "counts": counts,
            "runs": brk.sum().astype(i64),
            "stream": stream,
            "brk": brk,
        }
        if stream_rows is not None:
            hop["rows"] = stream_rows[out_perm]
            hop["stream_rows"] = stream_rows
    return hop


def _rank(seg, n: int, S: int, packable: bool):
    """The rank stage: the stable grouping permutation by segment, the
    grouped segment column, each segment's start and count, and each
    grouped slot's in-segment position."""
    import jax.numpy as jnp

    i64 = jnp.int64
    if packable:
        order, seg_g = _stable_perm(seg, n)
    else:
        order = jnp.argsort(seg, stable=True)
        seg_g = seg[order]
    # seg_g is sorted: each segment's edges are searches into it.
    edges = jnp.searchsorted(
        seg_g, jnp.arange(S + 1, dtype=i64), side="left"
    ).astype(i64)
    starts, counts = edges[:-1], edges[1:] - edges[:-1]
    q = jnp.arange(n, dtype=i64) - starts[seg_g]  # in-segment position
    return order, seg_g, starts, counts, q


def _ship_order(eidx, seg_g, q, counts, starts, n: int, P: int,
                packable: bool):
    """The ship stage's wire order: packets ship at their last key's
    emission.  Returns the permutation, the per-key ship indices and
    packet ordinals in grouped order."""
    import jax.numpy as jnp

    pkt_j = q // P
    last_q = jnp.minimum((pkt_j + 1) * P, counts[seg_g]) - 1
    ship_key = eidx[jnp.clip(starts[seg_g] + last_q, 0, max(n - 1, 0))]
    if packable:
        out_perm, _ = _stable_perm(ship_key, n)  # ship index < n: fits
    else:
        out_perm = jnp.argsort(ship_key, stable=True)
    return out_perm, ship_key, pkt_j


def _turns_and_breaks(seq_out, sid_out, seg_g, descents, n: int):
    """Each wire key's packet ordinal (the next hop's round-robin turn) and
    the grouped stream's run-break flags: a new segment, or a key below the
    one before it (``descents()``, ``stream[1:] < stream[:-1]``)."""
    import jax.numpy as jnp

    i64 = jnp.int64
    if not n:
        return jnp.zeros(0, i64), jnp.zeros(0, bool)
    chg = jnp.concatenate([
        jnp.ones(1, bool),
        (seq_out[1:] != seq_out[:-1]) | (sid_out[1:] != sid_out[:-1]),
    ])
    turn = jnp.cumsum(chg).astype(i64) - 1
    seg_chg = jnp.concatenate([jnp.ones(1, bool), seg_g[1:] != seg_g[:-1]])
    desc = jnp.concatenate([jnp.zeros(1, bool), descents()])
    return turn, seg_chg | desc


def _device_hop_wide(words, rows, bounds, S: int, L: int, P: int,
                     vbits: int, use_kernel: bool, interpret: bool | None):
    """:func:`_device_hop` for wide keys: ``words`` is a tuple of ``W``
    int32 sort-word columns (:func:`repro.core.partition.key_words`),
    ``rows`` the int32 input rows, ``bounds`` the ``(W, S - 1)`` words of
    the inner range bounds.

    The rows ride as the last sort word, so every order below is the
    lexicographic order of ``(words..., row)`` tuples: ties resolve by
    input row, and the all-max pad tuple sorts above every real one.
    Route counts the inner bounds at or below each key; rank, emit and
    ship are the one-word hop's; the block sort is the multi-word Pallas
    kernel (``lax.sort`` over the tuples without it); run breaks compare
    the tuples.  Each traced hop counts ``epoch_hop:wide_key:w<W>`` beside
    ``epoch_hop:scatter_free:int64``.
    """
    import jax
    import jax.numpy as jnp

    from ..kernels import ops
    from ..kernels.bitonic import lex_gt_lt

    i64 = jnp.int64
    n = int(words[0].shape[0])
    packable = vbits > 0
    W = len(words)
    ops.LOWERING_COUNTS["epoch_hop:scatter_free:int64"] += 1
    ops.LOWERING_COUNTS[f"epoch_hop:wide_key:w{W}"] += 1
    with jax.named_scope("route"):
        seg = jnp.zeros(n, i64)
        for b in range(S - 1):
            _, below = lex_gt_lt(words, tuple(bounds[:, b]))
            seg = seg + (~below).astype(i64)
    with jax.named_scope("rank"):
        order, seg_g, starts, counts, q = _rank(seg, n, S, packable)
        grouped = tuple(w[order] for w in words) + (rows[order],)

    with jax.named_scope("block_sort"):
        blocks = _BlockRows(seg_g, counts, starts, S, L)
        pad = jnp.iinfo(jnp.int32).max
        mats = tuple(blocks.to_rows(c, pad) for c in grouped)
        if use_kernel:
            srt = ops.sort_rows_padded(jnp.stack(mats), interpret=interpret)
            srt = tuple(srt[i] for i in range(W + 1))
        else:
            srt = jax.lax.sort(mats, dimension=1, num_keys=W + 1)
        stream = tuple(blocks.from_rows(m) for m in srt)

    with jax.named_scope("emit"):
        eidx = _emit_index(order, seg_g, q, counts, starts, L)

    with jax.named_scope("ship"):
        out_perm, ship_key, pkt_j = _ship_order(
            eidx, seg_g, q, counts, starts, n, P, packable
        )
        wire = tuple(c[out_perm] for c in stream)
        sid_out = seg_g[out_perm]
        seq_out = pkt_j[out_perm]
        turn, brk = _turns_and_breaks(
            seq_out, sid_out, seg_g,
            lambda: lex_gt_lt(
                tuple(c[1:] for c in stream), tuple(c[:-1] for c in stream)
            )[1],
            n,
        )
        return {
            "vals": wire[:W],
            "rows": wire[W],
            "seq": seq_out,
            "sid": sid_out,
            "turn": turn,
            "ship": ship_key[out_perm],
            "counts": counts,
            "runs": brk.sum().astype(i64),
            "stream": stream,
            "brk": brk,
        }


def _rr_merge(parts, carry_rows: bool, packable: bool):
    """Round-robin uplink interleave, traced.

    Parents concatenate in parent order; a stable argsort by per-key packet
    ordinal then equals ``lexsort((pos, src, turn))`` — the exact order
    :func:`repro.net.wire.merge_round_robin_batches` produces.  Packet
    ordinals are bounded by the merged key count, so the packed key-only
    sort (:func:`_stable_perm`) applies whenever the hop math is packable.
    """
    import jax.numpy as jnp

    if len(parts) == 1:
        p = parts[0]
        return p["vals"], (p["rows"] if carry_rows else None)
    turn = jnp.concatenate([p["turn"] for p in parts])
    m = int(turn.shape[0])
    if packable:
        order, _ = _stable_perm(turn, m)
    else:
        order = jnp.argsort(turn, stable=True)
    if isinstance(parts[0]["vals"], tuple):  # wide keys: word columns
        vals = tuple(
            jnp.concatenate([p["vals"][i] for p in parts])[order]
            for i in range(len(parts[0]["vals"]))
        )
    else:
        vals = jnp.concatenate([p["vals"] for p in parts])[order]
    rows = None
    if carry_rows:
        rows = jnp.concatenate([p["rows"] for p in parts])[order]
    return vals, rows


def _epoch_program(graph, spec: HopSpec, ranges: np.ndarray,
                   group_ns: tuple, carry_rows: bool, use_kernel: bool,
                   interpret: bool | None, taps: bool, tracer=NULL_TRACER):
    """Fetch the jitted whole-epoch program from the cache, or build it in
    an ``epoch:build`` span (jax compiles it at its first dispatch).

    ``ranges`` participates in the key by value — HopSpec deliberately
    excludes it from comparison, but two specs differing only in their
    installed ranges compile different routing cascades.
    """
    key = (
        graph, spec.num_segments, spec.segment_length, spec.payload_size,
        ranges.dtype.str, ranges.tobytes(), group_ns, carry_rows,
        use_kernel, interpret, taps,
    )
    fn = _PROGRAM_CACHE.get(key)
    if fn is None:
        with tracer.span("epoch:build", cat="epoch", hops=len(graph.nodes)):
            fn = _build_program(
                graph, spec, ranges, group_ns, carry_rows, use_kernel,
                interpret, taps,
            )
        if len(_PROGRAM_CACHE) >= _PROGRAM_CACHE_MAX:
            _PROGRAM_CACHE.pop(next(iter(_PROGRAM_CACHE)))
        _PROGRAM_CACHE[key] = fn
    return fn


def _build_program(graph, spec: HopSpec, ranges: np.ndarray,
                   group_ns: tuple, carry_rows: bool, use_kernel: bool,
                   interpret: bool | None, taps: bool):
    """The jitted whole-epoch program: each hop in a ``hop_<name>`` named
    scope, each uplink merge in ``uplink_merge``.

    A wide-key table (:func:`_is_wide`) builds the wide program: its
    ingress values are ``(W, n)`` sort words, its rows int32, and it
    returns the egress words stacked ``(W, n)`` and the grouped stream
    stacked with its rows ``(W + 1, n)``."""
    import jax
    import jax.numpy as jnp

    S, L, P = spec.num_segments, spec.segment_length, spec.payload_size
    nodes = graph.nodes
    if _is_wide(ranges):
        return _build_wide_program(
            nodes, S, L, P, ranges, group_ns, use_kernel, interpret
        )
    bounds_np = np.ascontiguousarray(ranges[:, 1], dtype=np.int64)
    # Packed-sort feasibility: every stable permutation in the epoch rides
    # a key-only sort of ``(key << bits(n)) | index`` when the domain is
    # non-negative and key+index fit in 63 bits; otherwise vbits=0 selects
    # the (byte-identical, slower) stable-argsort fallbacks.
    vmax_dom = int(ranges[-1, 1]) - 1
    n_total = int(sum(group_ns))
    nbits = max(1, (n_total - 1).bit_length()) if n_total > 1 else 1
    vbits = max(1, vmax_dom.bit_length())
    if int(ranges[0, 0]) < 0 or vmax_dom < 0 or nbits > 31:
        vbits = 0

    def epoch_fn(ingress_vals, ingress_rows):
        bounds = jnp.asarray(bounds_np)
        hops = []
        for node in nodes:
            with jax.named_scope(f"hop_{node.name}"):
                if node.parents:
                    with jax.named_scope("uplink_merge"):
                        vals, rows = _rr_merge(
                            [hops[p] for p in node.parents], carry_rows,
                            vbits > 0,
                        )
                else:
                    vals = ingress_vals[node.group]
                    rows = ingress_rows[node.group] if carry_rows else None
                hops.append(
                    _device_hop(
                        vals, rows, bounds, S, L, P, vbits, use_kernel,
                        interpret,
                    )
                )
        eg = hops[-1]
        res = {
            "vals": eg["vals"],
            "seq": eg["seq"],
            "sid": eg["sid"],
            "counts": tuple(h["counts"] for h in hops),
            "runs": tuple(h["runs"] for h in hops),
            "stream": eg["stream"],
            "brk": eg["brk"],
        }
        if carry_rows:
            res["rows"] = eg["rows"]
            res["stream_rows"] = eg["stream_rows"]
        if taps:
            res["taps"] = tuple(
                {
                    k: h[k]
                    for k in (
                        ("vals", "seq", "sid", "ship", "rows")
                        if carry_rows
                        else ("vals", "seq", "sid", "ship")
                    )
                }
                for h in hops
            )
        return res

    return jax.jit(epoch_fn, donate_argnums=(0, 1))


def _is_wide(ranges: np.ndarray) -> bool:
    """Whether ``ranges`` is a wide-key table: the int32 sort words of
    each range's lower bound (:func:`repro.core.partition.set_ranges_wide`),
    not int64 ``[lo, hi)`` pairs."""
    return ranges.dtype == np.int32


def _build_wide_program(nodes, S: int, L: int, P: int, ranges: np.ndarray,
                        group_ns: tuple, use_kernel: bool,
                        interpret: bool | None):
    """:func:`_build_program` for wide keys (:func:`_device_hop_wide`)."""
    import jax
    import jax.numpy as jnp

    bounds_np = np.ascontiguousarray(ranges[1:].T)  # (W, S - 1)
    W = bounds_np.shape[0]
    n_total = int(sum(group_ns))
    vbits = 32 if n_total <= 1 << 31 else 0

    def epoch_fn(ingress_vals, ingress_rows):
        bounds = jnp.asarray(bounds_np)
        hops = []
        for node in nodes:
            with jax.named_scope(f"hop_{node.name}"):
                if node.parents:
                    with jax.named_scope("uplink_merge"):
                        vals, rows = _rr_merge(
                            [hops[p] for p in node.parents], True, vbits > 0
                        )
                else:
                    g = ingress_vals[node.group]
                    vals = tuple(g[i] for i in range(W))
                    rows = ingress_rows[node.group]
                hops.append(
                    _device_hop(
                        vals, rows, bounds, S, L, P, vbits, use_kernel,
                        interpret,
                    )
                )
        eg = hops[-1]
        return {
            "vals": jnp.stack(eg["vals"]),
            "rows": eg["rows"],
            "seq": eg["seq"],
            "sid": eg["sid"],
            "counts": tuple(h["counts"] for h in hops),
            "runs": tuple(h["runs"] for h in hops),
            "stream": jnp.stack(eg["stream"]),
            "brk": eg["brk"],
        }

    return jax.jit(epoch_fn, donate_argnums=(0, 1))


# ---------------------------------------------------------------------------
# Host orchestration
# ---------------------------------------------------------------------------


def _stats_from_device(name: str, counts: np.ndarray, runs: int,
                       L: int) -> HopStats:
    """HopStats scalars from the device-computed per-hop reductions —
    field-for-field equal to :meth:`HopStats._from_grouped`'s scalars."""
    counts = np.asarray(counts, dtype=np.int64)
    total = int(counts.sum())
    runs = int(runs)
    recirc = int(
        np.where(
            counts == 0,
            0,
            np.where((counts <= L) | (counts % L == 0), 1, 2),
        ).sum()
    )
    return HopStats(
        name=name,
        arrivals=total,
        segment_loads=counts,
        load_imbalance=float(counts.max() / counts.mean()) if total else 1.0,
        emitted_runs=runs,
        mean_run_len=(total / runs) if runs else 0.0,
        recirculations=recirc,
    )


def run_graph_device(
    graph,
    batch: WireBatch,
    spec: HopSpec,
    *,
    tracer=None,
    metrics=None,
    int_telemetry: bool = False,
    network=None,
    ship_emission: bool = False,
):
    """Execute a fabric epoch as one compiled device program.

    Drop-in for :func:`repro.net.topology.run_graph` with
    ``engine="device"`` — same return contract, byte-identical outputs and
    (scalar-)equal per-hop stats.  Exactly one host→device transfer (the
    donated ingress buffers) and one device→host transfer (the result
    pytree) happen per call, counted in :data:`TRANSFER_COUNTS`.
    ``ship_emission=True`` fetches the per-hop taps of an observed run even
    when nothing observes it, so every hop's stats carry their packets'
    ship indices (the ``run_hop`` contract of :func:`device_hop`).  A
    ``tracer`` only adds spans: it never selects the observed program.
    """
    tr = tracer or NULL_TRACER
    if int_telemetry or batch.int_meta is not None:
        raise ValueError(
            "engine 'device' does not support INT telemetry — the compiled "
            "epoch never materializes the per-hop streams the stamp needs; "
            "use the 'fused' engine for INT runs"
        )
    if len(batch) == 0:
        # Nothing to compile for a drained epoch; the per-hop loop on an
        # empty stream is already output- and stats-identical.
        from .topology import run_graph

        return run_graph(
            graph, batch, spec, "fused",
            tracer=tracer, metrics=metrics, network=network,
        )
    import jax

    from ..core.partition import set_ranges
    from ..kernels.ops import x64_scope

    carry_rows = batch.row_index is not None
    collect = ship_emission or network is not None or metrics is not None
    ranges = spec.ranges
    if ranges is None:
        ranges = set_ranges(spec.max_value, spec.num_segments)
    L = spec.segment_length
    pow2_blocks = L > 1 and not (L & (L - 1))
    wide = _is_wide(ranges)
    if wide:
        # Wide keys: every key of B bytes lies in the table's domain; the
        # rows ride as the last sort word, so the Pallas block sort takes
        # them as it takes the words.
        if batch.values.ndim != 2 or not carry_rows:
            raise ValueError(
                "a wide-key table needs sort-word values and their rows"
            )
        if collect:
            raise ValueError(
                "wide keys run the device epoch unobserved: no metrics, "
                "network or per-hop taps"
            )
        use_kernel = spec.backend == "pallas" and pow2_blocks
    else:
        # Domain check once at ingress (interior hops see the same
        # multiset).
        vmin = int(batch.values.min())
        vmax = int(batch.values.max())
        if vmin < int(ranges[0, 0]) or vmax >= int(ranges[-1, 1]):
            raise ValueError("value outside the switch domain")
        use_kernel = (
            spec.backend == "pallas"
            and not carry_rows
            and pow2_blocks
            and vmin >= 0
            and vmax < np.iinfo(np.int32).max
        )

    with x64_scope():
        # ``epoch:put`` holds the whole ingress layout: the split into
        # ingress groups and the one copy of their columns.
        with tr.span("epoch:put", cat="epoch") as put:
            ingress = split_by_flow(batch, graph.num_groups)
            if wide:  # word columns as rows of one (W, n) array
                host_args = (
                    tuple(np.ascontiguousarray(g.values.T) for g in ingress),
                    tuple(g.row_index.astype(np.int32) for g in ingress),
                )
            else:
                host_args = (
                    tuple(np.ascontiguousarray(g.values) for g in ingress),
                    tuple(np.ascontiguousarray(g.row_index) for g in ingress)
                    if carry_rows
                    else (),
                )
            put.set(bytes=nbytes(host_args))
            dev_args = to_device(host_args)
        fn = _epoch_program(
            graph, spec, ranges, tuple(len(g) for g in ingress), carry_rows,
            use_kernel, KERNEL_INTERPRET, collect, tr,
        )
        with warnings.catch_warnings():
            # The CPU backend cannot always reuse donated input buffers and
            # says so; donation is a no-op there, not an error.  On real
            # accelerators the ingress buffers are consumed in place.
            warnings.filterwarnings(
                "ignore", message="Some donated buffers were not usable"
            )
            with tr.span("epoch:device", cat="epoch"):
                dev_res = jax.block_until_ready(fn(*dev_args))
        # ``epoch:fetch`` holds the result's copy and its assembly into the
        # egress wire batch on the host.
        with tr.span("epoch:fetch", cat="epoch", bytes=nbytes(dev_res)):
            res = fetch(dev_res)
            stats = [
                _stats_from_device(
                    node.name, res["counts"][i], res["runs"][i], L
                )
                for i, node in enumerate(graph.nodes)
            ]
            # Wide keys come back stacked by column: the transposes are
            # (n, W) views, no copy.
            vals = res["vals"].T if wide else res["vals"]
            delivery = DeviceDelivery(
                vals,
                np.full(len(vals), len(graph.nodes) - 1, dtype=np.int64),
                res["seq"],
                res["sid"],
                epoch=batch.epoch,
                row_index=res.get("rows"),
                grouped_values=res["stream"].T if wide else res["stream"],
                grouped_rows=res.get("stream_rows"),
                seg_counts=np.asarray(res["counts"][-1], dtype=np.int64),
                run_flags=res["brk"],
            )
    if not collect:
        return delivery, stats

    # -- observed run: replay the per-hop bookkeeping from the taps -----
    from .topology import _emitted_run_lengths

    timer = None
    if network is not None:
        from .timing import GraphTimer

        timer = GraphTimer(
            graph, batch, network, tracer=tracer, metrics=metrics
        )
    outs: list[WireBatch] = []
    for i, node in enumerate(graph.nodes):
        tap = res["taps"][i]
        out = WireBatch(
            tap["vals"],
            np.full(int(tap["vals"].size), i, dtype=np.int64),
            tap["seq"],
            tap["sid"],
            epoch=batch.epoch,
            row_index=tap.get("rows"),
        )
        if node.parents:
            inp = merge_round_robin_batches([outs[p] for p in node.parents])
        else:
            inp = ingress[node.group]
        pstarts = out.packet_starts()
        stats[i] = dataclasses.replace(
            stats[i], ship_emission=np.asarray(tap["ship"])[pstarts]
        )
        st = stats[i]
        if metrics is not None:
            metrics.counter("hop_keys_in", node.name).inc(len(inp))
            metrics.counter("hop_keys_out", node.name).inc(len(out))
            metrics.counter("hop_packets_out", node.name).inc(out.num_packets)
            metrics.counter("hop_recirculations", node.name).inc(
                st.recirculations
            )
            metrics.gauge("hop_segment_loads", node.name).set(st.segment_loads)
            metrics.gauge("hop_load_imbalance", node.name).set(
                st.load_imbalance
            )
            metrics.histogram("hop_emitted_run_length", node.name).observe_many(
                _emitted_run_lengths(out)
            )
        if timer is not None:
            timer.after_hop(i, node, inp, out, st, outs)
        outs.append(out)
    if timer is not None:
        delivered, report = timer.egress_deliver(outs[-1])
        return delivered, stats, report
    return delivery, stats


def device_hop(
    batch: WireBatch,
    spec: HopSpec,
    name: str,
    *,
    tracer=None,
    hop_id: int = 0,
    int_telemetry: bool = False,
) -> tuple[WireBatch, HopStats]:
    """Single-hop view of the compiled epoch (the ``run_hop`` contract:
    output flow ids are 0; the graph scheduler restamps them)."""
    del hop_id
    from .topology import HopGraph, HopNode

    if len(batch) == 0:
        out = empty_batch(batch.epoch)
        if batch.row_index is not None:
            out = out.with_row_index(np.zeros(0, dtype=np.int64))
        st = _stats_from_device(
            name,
            np.zeros(spec.num_segments, dtype=np.int64),
            0,
            spec.segment_length,
        )
        st = dataclasses.replace(
            st, ship_emission=np.zeros(0, dtype=np.int64)
        )
        return out, st
    graph = HopGraph((HopNode(name),), num_groups=1)
    if int_telemetry or batch.int_meta is not None:
        raise ValueError(
            "engine 'device' does not support INT telemetry — use 'fused'"
        )
    out, stats = run_graph_device(
        graph, batch, spec, tracer=tracer, ship_emission=True
    )
    return out, stats[0]


def device_self_check(interpret: bool = True, n: int = 4096,
                      seed: int = 0) -> None:
    """CPU check: run a small epoch with the Pallas block-sort kernel forced
    (``interpret=True`` exercises the kernel path on CPU-only runners) and
    assert byte-identity against the fused per-hop engine.

    Interpret mode proves nothing about the chip — not that Mosaic lowers
    the kernel, nor what it computes there.  ``chip_smoke.py`` at the repo
    root is the chip's check.
    """
    global KERNEL_INTERPRET
    from .topology import leaf_spine_graph, run_graph
    from ..core.partition import set_ranges

    rng = np.random.default_rng(seed)
    max_value = (1 << 20) - 1
    values = rng.integers(0, max_value + 1, n)
    from .flow import interleave_batch, split_flows

    arrivals = interleave_batch(split_flows(values, 4, 32), "round_robin")
    spec = HopSpec(
        8, 32, max_value, set_ranges(max_value, 8),
        payload_size=32, backend="pallas",
    )
    graph = leaf_spine_graph(2)
    ref, ref_stats = run_graph(graph, arrivals, spec, "fused")
    prev = KERNEL_INTERPRET
    KERNEL_INTERPRET = interpret
    try:
        out, stats = run_graph_device(graph, arrivals, spec)
    finally:
        KERNEL_INTERPRET = prev
    np.testing.assert_array_equal(out.values, ref.values)
    np.testing.assert_array_equal(out.seq, ref.seq)
    np.testing.assert_array_equal(out.segment_id, ref.segment_id)
    assert stats == ref_stats, "device stats diverge from fused"
