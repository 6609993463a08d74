"""The computation server: k-way *natural* merge sort (paper Alg. 1, §4.3.2).

Natural = the initial runs are the maximal ascending sub-sequences already
present in the input, which is where MergeMarathon's pre-processing pays:
longer initial runs ⇒ fewer merge passes (``log_k(N / r̃_init)``).

Two implementations:

* ``merge_sort`` — production path: vectorized two-way merges arranged as a
  tournament inside each k-set.  A pass over the data is O(N) vectorized
  work per tree level; the pass structure (and therefore the *relative*
  benefit of longer runs, the paper's metric) matches the paper's k-way
  merge.
* ``merge_sort_reference`` — pure-python k-way merge with an explicit k-ary
  min selection, literally Alg. 1 / Fig. 6, for tests on small inputs.

``server_sort`` is the full paper server: sort each switch segment's
sub-stream independently, then concatenate by segment id (ranges are
non-overlapping and ordered, so concatenation is the final answer).
"""

from __future__ import annotations

import numpy as np

from repro.obs.trace import NULL_TRACER
from repro.transfers import fetch, to_device

from .partition import _TOP_BIT
from .runs import run_starts


def merge_two(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Vectorized stable merge of two sorted arrays (Fig. 6's inner loop)."""
    n, m = a.size, b.size
    if n == 0 or m == 0:
        keep = b if n == 0 else a
        if a.dtype == b.dtype:
            # Same dtype: no result_type promotion and no per-round copy —
            # a contiguous input passes straight through as a view.
            return np.ascontiguousarray(keep)
        return keep.astype(np.result_type(a, b))
    out = np.empty(n + m, dtype=np.result_type(a, b))
    # Output position of each b element: elements of a strictly <= go first.
    ib = np.searchsorted(a, b, side="right") + np.arange(m)
    mask = np.ones(n + m, dtype=bool)
    mask[ib] = False
    out[ib] = b
    out[mask] = a
    return out


def merge_runs(runs: list[np.ndarray]) -> np.ndarray:
    """Merge sorted runs into one via a tournament of two-way merges.

    Runs of wide keys (2-D: one row of int32 sort words per key) merge as
    byte strings whose memcmp order is the rows' lexicographic order."""
    if runs and np.ndim(runs[0]) == 2:
        width = runs[0].shape[1]
        return _from_bytes(merge_runs([_as_bytes(r) for r in runs]), width)
    while len(runs) > 1:
        nxt = []
        for i in range(0, len(runs) - 1, 2):
            nxt.append(merge_two(runs[i], runs[i + 1]))
        if len(runs) % 2:
            nxt.append(runs[-1])
        runs = nxt
    return runs[0]


def _as_bytes(words: np.ndarray) -> np.ndarray:
    """Rows of int32 sort words as fixed-width byte strings: big-endian
    unsigned words, so memcmp order is the rows' lexicographic order (numpy
    compares equal-width byte strings bytewise)."""
    be = (np.asarray(words, dtype=np.int32) ^ _TOP_BIT).view(np.uint32)
    be = np.ascontiguousarray(be.astype(">u4"))
    return be.view(f"S{4 * be.shape[1]}").ravel()


def _from_bytes(strings: np.ndarray, width: int) -> np.ndarray:
    """Inverse of :func:`_as_bytes`."""
    be = np.ascontiguousarray(strings).view(">u4").reshape(-1, width)
    return be.astype(np.uint32).view(np.int32) ^ _TOP_BIT


def _merge_set(arr: np.ndarray, starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Merge the runs arr[starts[i]:ends[i]] (each sorted) into one run."""
    return merge_runs([arr[s:e] for s, e in zip(starts, ends)])


# ---------------------------------------------------------------------------
# Batched device merge: the run-arena engine
# ---------------------------------------------------------------------------

#: Below this many keys the host ladder wins — one jit dispatch costs more
#: than the whole merge (and small test inputs never touch the jit cache).
MIN_DEVICE_KEYS = 4096


def _next_pow2(n: int) -> int:
    return 1 << (int(n) - 1).bit_length()


def _ragged_gather(starts: np.ndarray, sizes: np.ndarray) -> np.ndarray:
    """Flat indices of the slices ``[starts[i], starts[i]+sizes[i])``."""
    sizes = np.asarray(sizes, dtype=np.int64)
    total = int(sizes.sum())
    if total == 0:
        return np.zeros(0, dtype=np.int64)
    offs = np.concatenate([[0], np.cumsum(sizes)[:-1]])
    rel = np.arange(total, dtype=np.int64) - np.repeat(offs, sizes)
    return np.repeat(np.asarray(starts, dtype=np.int64), sizes) + rel


def _device_dtype(lo: int, hi: int) -> np.dtype | None:
    """Narrowest device dtype whose *max* can serve as the pad sentinel.

    Mirrors :func:`repro.net.engine.pallas_row_sort`'s overflow rule: a real
    key at the sentinel would be indistinguishable from padding, so it drops
    to the numpy ladder rather than lean on multiset arguments.  Keys beyond
    int32 — the packed key+payload-row records of the device dataplane —
    merge as int64, which the tournament runs under an x64 scope.  Wide
    records (keys wider than 64 bits) never come here: their rows of int32
    sort words, the input row last, merge as stacked int32 words in
    :func:`merge_runs_flat`, where an all-max pad tuple sorts above every
    real row.
    """
    if 0 <= lo and hi < np.iinfo(np.uint16).max:
        return np.dtype(np.uint16)
    if np.iinfo(np.int32).min < lo and hi < np.iinfo(np.int32).max:
        return np.dtype(np.int32)
    if np.iinfo(np.int64).min < lo and hi < np.iinfo(np.int64).max:
        return np.dtype(np.int64)
    return None


def merge_runs_flat(
    buf: np.ndarray,
    starts: np.ndarray,
    lengths: np.ndarray,
    *,
    min_device_keys: int = MIN_DEVICE_KEYS,
    interpret: bool | None = None,
    tracer=None,
    tid: int = 0,
) -> np.ndarray:
    """Merge the sorted runs ``buf[starts[i]:starts[i]+lengths[i]]`` — the
    run-arena layout — into one sorted int64 array, on device.

    Runs are bucketed by power-of-two length, each bucket is laid out as one
    padded ``(P, B)`` matrix (two vectorized ragged gathers — runs are never
    touched individually) and merged to a single row by
    :func:`repro.kernels.ops.merge_tournament`; the handful of bucket
    winners then merge on the host.  Power-of-two P and B are what keep the
    jit cache to a few compiled shapes across ladder levels.

    Exactly like ``sort_rows_padded``'s callers, anything the device path
    cannot represent falls back to the numpy ladder (:func:`merge_runs` of
    :func:`merge_two`): key ranges that do not fit the int32/uint16 pad
    sentinels, or totals too small to amortize a dispatch.

    ``tracer`` records one ``tournament:b<B>`` span per tournament call,
    whose ``bytes`` are its matrix's copy to the device and back (both made
    through :mod:`repro.transfers`, and counted there), and a ``winners``
    span for the final host merge (cat="server", lane ``tid``).
    """
    tr = tracer or NULL_TRACER
    starts = np.asarray(starts, dtype=np.int64)
    lengths = np.asarray(lengths, dtype=np.int64)
    keep = lengths > 0
    if not keep.all():
        starts, lengths = starts[keep], lengths[keep]
    if np.ndim(buf) == 2:
        return _merge_runs_flat_wide(
            buf, starts, lengths, min_device_keys, interpret, tr, tid
        )
    R = int(starts.size)
    if R == 0:
        return np.zeros(0, dtype=np.int64)
    if R == 1:
        s = int(starts[0])
        return np.asarray(buf[s : s + int(lengths[0])], dtype=np.int64)
    total = int(lengths.sum())
    # Runs are sorted, so per-run min/max are the end keys: O(R), not O(n).
    lo = int(buf[starts].min())
    hi = int(buf[starts + lengths - 1].max())
    dtype = _device_dtype(lo, hi)
    if total < min_device_keys or dtype is None:
        return np.asarray(
            merge_runs([buf[s : s + l] for s, l in zip(starts, lengths)]),
            dtype=np.int64,
        )
    from ..kernels import bitonic, ops  # deferred: jax import is heavy

    if dtype.itemsize == 8:
        # 64-bit keys (packed key+payload-row records): the tournament must
        # run under an x64 scope, or jax would silently truncate to int32.
        _merge_scope = ops.x64_scope
    else:
        import contextlib

        _merge_scope = contextlib.nullcontext
    pad = dtype.type(np.iinfo(dtype).max)
    winners: list[np.ndarray] = []
    for B, part in _bucket_parts(lengths, bitonic.TOURNAMENT_MAX_ELEMS):
        P = int(part.size)
        if P == 1:
            i = int(part[0])
            winners.append(buf[starts[i] : starts[i] + lengths[i]])
            continue
        rows = max(2, _next_pow2(P))
        with tr.span(
            f"tournament:b{B}", cat="server", tid=tid, runs=P,
            bytes=2 * rows * B * dtype.itemsize,
        ):
            sl = lengths[part]
            mat = np.full((rows, B), pad, dtype)
            mat.flat[_ragged_gather(np.arange(P) * B, sl)] = buf[
                _ragged_gather(starts[part], sl)
            ]
            with _merge_scope():
                merged = fetch(
                    ops.merge_tournament(
                        to_device(mat), interpret=interpret
                    )
                )
            winners.append(merged[: int(sl.sum())])
    if len(winners) == 1:
        return winners[0].astype(np.int64)
    with tr.span("winners", cat="server", tid=tid, runs=len(winners)):
        return np.asarray(merge_runs(winners), dtype=np.int64)


def _bucket_parts(lengths: np.ndarray, cap: int):
    """``(B, members)`` of each tournament call: runs bucketed by
    power-of-two length ``B``, a bucket split so that a call holds at most
    ``cap`` keys (its parts meet in the host merge of the winners)."""
    # Vectorized next-pow2 (float64 log2 is exact for any realistic length).
    buckets = (2 ** np.ceil(np.log2(lengths))).astype(np.int64)
    for B in np.unique(buckets):
        B = int(B)
        members = np.nonzero(buckets == B)[0]
        per_call = max(1, cap // B)
        for first in range(0, members.size, per_call):
            yield B, members[first : first + per_call]


def _merge_runs_flat_wide(buf, starts, lengths, min_device_keys, interpret,
                          tr, tid) -> np.ndarray:
    """:func:`merge_runs_flat` of wide records: ``buf`` holds one int32 row
    of ``K`` sort words per key (the input row last, so rows are distinct).
    Each tournament merges the ``K`` words stacked ``(K, P, B)`` as tuples
    (:func:`repro.kernels.ops.merge_tournament`, in XLA), pads all-max, and
    holds at most :data:`~repro.kernels.bitonic.TOURNAMENT_MAX_ELEMS` words
    as the one-word kernel holds keys, which keeps the rows' padding to a
    power of two small; returns ``(n, K)`` int32."""
    K = buf.shape[1]
    R = int(starts.size)
    if R == 0:
        return np.zeros((0, K), dtype=np.int32)
    if R == 1:
        s = int(starts[0])
        return buf[s : s + int(lengths[0])].copy()
    if int(lengths.sum()) < min_device_keys:
        return merge_runs([buf[s : s + l] for s, l in zip(starts, lengths)])
    from ..kernels import bitonic, ops  # deferred: jax import is heavy

    pad = np.iinfo(np.int32).max
    winners: list[np.ndarray] = []
    cap = 1 << ((bitonic.TOURNAMENT_MAX_ELEMS // K).bit_length() - 1)
    for B, part in _bucket_parts(lengths, cap):
        P = int(part.size)
        if P == 1:
            i = int(part[0])
            winners.append(buf[starts[i] : starts[i] + lengths[i]])
            continue
        rows = max(2, _next_pow2(P))
        with tr.span(
            f"tournament:b{B}", cat="server", tid=tid, runs=P,
            bytes=2 * K * rows * B * 4,
        ):
            sl = lengths[part]
            m = int(sl.sum())
            mat = np.full((K, rows * B), pad, np.int32)
            mat[:, _ragged_gather(np.arange(P) * B, sl)] = np.take(
                buf, _ragged_gather(starts[part], sl), axis=0
            ).T
            merged = fetch(
                ops.merge_tournament(
                    to_device(mat.reshape(K, rows, B)), interpret=interpret
                )
            )
            winners.append(merged[:, :m].T)
    if len(winners) == 1:
        return np.ascontiguousarray(winners[0])
    with tr.span("winners", cat="server", tid=tid, runs=len(winners)):
        return merge_runs(winners)


def merge_runs_batched(
    runs: list[np.ndarray],
    *,
    min_device_keys: int = MIN_DEVICE_KEYS,
    interpret: bool | None = None,
    tracer=None,
    tid: int = 0,
) -> np.ndarray:
    """Device twin of :func:`merge_runs` for a list of sorted arrays.

    Concatenates the runs into the flat arena layout once and defers to
    :func:`merge_runs_flat`; used where the runs are not already contiguous
    (the epoched ``final_merge`` of per-segment outputs).
    """
    runs = [r for r in runs if r.size]
    if not runs:
        return np.zeros(0, dtype=np.int64)
    if len(runs) == 1:
        return np.asarray(runs[0], dtype=np.int64)
    lengths = np.asarray([r.size for r in runs], dtype=np.int64)
    starts = np.concatenate([[0], np.cumsum(lengths)[:-1]])
    return merge_runs_flat(
        np.concatenate(runs),
        starts,
        lengths,
        min_device_keys=min_device_keys,
        interpret=interpret,
        tracer=tracer,
        tid=tid,
    )


def merge_sort(a: np.ndarray, k: int = 10) -> tuple[np.ndarray, int]:
    """Natural k-way merge sort.  Returns (sorted array, number of passes)."""
    a = np.ascontiguousarray(a)
    if a.size <= 1:
        return a.copy(), 0
    starts = run_starts(a)
    passes = 0
    cur = a
    while starts.size > 1:
        ends = np.concatenate([starts[1:], [cur.size]])
        new_parts = []
        new_starts = [0]
        # Stage 1 of Alg. 1: group runs into sets of k; Stage 2: merge each.
        for g in range(0, starts.size, k):
            merged = _merge_set(cur, starts[g : g + k], ends[g : g + k])
            new_parts.append(merged)
            new_starts.append(new_starts[-1] + merged.size)
        cur = np.concatenate(new_parts)
        starts = np.asarray(new_starts[:-1], dtype=np.int64)
        passes += 1
    return cur, passes


def merge_sort_reference(a: np.ndarray, k: int = 10) -> np.ndarray:
    """Pure-python Alg. 1 with explicit k-ary min selection (Fig. 6)."""
    runs: list[list[int]] = []
    cur: list[int] = []
    prev = None
    for v in a:
        if prev is not None and v < prev:
            runs.append(cur)
            cur = []
        cur.append(int(v))
        prev = v
    if cur:
        runs.append(cur)
    while len(runs) > 1:
        nxt = []
        for g in range(0, len(runs), k):
            group = [list(r) for r in runs[g : g + k]]
            merged: list[int] = []
            idx = [0] * len(group)
            while True:
                # "the minimum among the first element of each Run"
                best, bv = -1, None
                for j, r in enumerate(group):
                    if idx[j] < len(r) and (bv is None or r[idx[j]] < bv):
                        best, bv = j, r[idx[j]]
                if best < 0:
                    break
                merged.append(bv)
                idx[best] += 1
            nxt.append(merged)
        runs = nxt
    return np.asarray(runs[0] if runs else [], dtype=np.int64)


def server_sort(
    streams: list[np.ndarray], k: int = 10
) -> tuple[np.ndarray, list[int]]:
    """§4.3.2: sort each segment separately, concatenate by segment id.

    Returns (fully sorted output, per-segment pass counts).
    """
    outs = []
    passes = []
    for sub in streams:
        s, p = merge_sort(sub, k=k)
        outs.append(s)
        passes.append(p)
    if not outs:
        return np.zeros(0, dtype=np.int64), []
    return np.concatenate(outs), passes
