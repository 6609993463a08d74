"""Range partitioning (Alg. 2 ``SetRanges``) + beyond-paper balanced ranges.

The paper splits the key domain into ``S`` contiguous ranges of (nearly)
equal *width*: ``q = max_value // S``, remainder ``r`` spread over the first
``r`` segments.  Equal-width ranges are what a switch can evaluate with plain
comparisons; they are also badly *load*-unbalanced on skewed traces (the
paper's network trace has 1,475 unique values concentrated in a narrow band).
We therefore also provide quantile (sampled-splitter) ranges, used by the
distributed sorter — the control plane computes them and dictates them to the
data plane, exactly the split the paper proposes for the division op.
"""

from __future__ import annotations

import numpy as np


def set_ranges(max_value: int, num_segments: int) -> np.ndarray:
    """Paper Alg. 2: equal-width half-open ranges covering [0, max_value].

    Returns ``(num_segments, 2)`` int64 array of ``[lo, hi)`` pairs with
    ``hi[-1] == max_value + 1``.  First ``r`` segments have width ``q+1``,
    the rest width ``q`` (``q, r = divmod(max_value + 1, num_segments)``).
    """
    if num_segments <= 0:
        raise ValueError("num_segments must be positive")
    domain = max_value + 1  # values are integers in [0, max_value]
    q, r = divmod(domain, num_segments)
    if q == 0:
        raise ValueError(
            f"more segments ({num_segments}) than domain values ({domain})"
        )
    widths = np.full(num_segments, q, dtype=np.int64)
    widths[:r] += 1
    hi = np.cumsum(widths)
    lo = hi - widths
    return np.stack([lo, hi], axis=1)


#: Key widths, in bytes, that ride as sort words (:func:`key_words`).
WIDE_KEY_BYTES = range(9, 17)

#: XOR with this flips a 32-bit word's top bit: unsigned order as int32's.
_TOP_BIT = np.int32(-(1 << 31))


def key_words(keys: np.ndarray) -> np.ndarray:
    """``(n, B)`` uint8 keys as ``(n, ceil(B/4))`` int32 *sort words*.

    Word ``i`` is key bytes ``4i .. 4i+3`` read as a big-endian uint32
    (the last word zero-filled), its top bit flipped, so signed int32
    order is the bytes' unsigned order and the words compared
    lexicographically keep the keys' memcmp order.
    """
    keys = np.asarray(keys, dtype=np.uint8)
    n, b = keys.shape
    w = -(-b // 4)
    padded = np.zeros((n, 4 * w), dtype=np.uint8)
    padded[:, :b] = keys
    return padded.view(">u4").astype(np.uint32).view(np.int32) ^ _TOP_BIT


def words_to_keys(words: np.ndarray, key_bytes: int) -> np.ndarray:
    """Inverse of :func:`key_words`: sort words back to ``(n, key_bytes)``
    uint8 keys."""
    words = np.asarray(words, dtype=np.int32)
    be = (words ^ _TOP_BIT).view(np.uint32).astype(">u4")
    return np.ascontiguousarray(
        np.ascontiguousarray(be).view(np.uint8)[:, :key_bytes]
    )


def set_ranges_wide(key_bytes: int, num_segments: int) -> np.ndarray:
    """Alg. 2's equal-width table over the whole domain ``[0, 256**B)`` of
    ``B``-byte keys, as the ``(S, W)`` sort words of each range's lower
    bound (row 0 is all zero bytes).

    The bounds are exact Python ints, ``q, r = divmod(256**B, S)`` with
    the first ``r`` ranges one wider, as :func:`set_ranges` splits.  A key's
    segment is the number of rows 1..S-1 at or below it, compared
    lexicographically.
    """
    if key_bytes not in WIDE_KEY_BYTES:
        raise ValueError(f"wide keys are 9 to 16 bytes, got {key_bytes}")
    if num_segments <= 0:
        raise ValueError("num_segments must be positive")
    q, r = divmod(256**key_bytes, num_segments)
    lows = [s * q + min(s, r) for s in range(num_segments)]
    raw = np.array(
        [list(v.to_bytes(key_bytes, "big")) for v in lows], dtype=np.uint8
    )
    return key_words(raw)


def segment_of(values: np.ndarray, ranges: np.ndarray) -> np.ndarray:
    """Vectorized SwitchInsert routing: which segment owns each value.

    On the switch this is the parse-stage comparison cascade; here a
    ``searchsorted`` over the range boundaries.
    """
    bounds = ranges[:, 1]  # exclusive upper bounds, ascending
    seg = np.searchsorted(bounds, values, side="right")
    if values.size and (
        int(values.min()) < int(ranges[0, 0]) or int(seg.max()) >= len(ranges)
    ):
        raise ValueError("value outside the switch domain")
    return seg.astype(np.int64)


def load_imbalance(values: np.ndarray, ranges: np.ndarray) -> float:
    """Peak-over-mean segment load of routing ``values`` through ``ranges``.

    1.0 is perfect balance; ``len(ranges)`` is everything on one segment.
    This is the §6.3 imbalance statistic as a *prediction*: the adaptive
    control plane evaluates it on a traffic sample to decide whether the
    installed ranges still fit the distribution (drift detection).
    """
    values = np.asarray(values)
    if values.size == 0:
        return 1.0
    counts = np.bincount(segment_of(values, ranges), minlength=len(ranges))
    return float(counts.max() / (values.size / len(ranges)))


def quantile_ranges(
    sample: np.ndarray, num_segments: int, max_value: int
) -> np.ndarray:
    """Balanced (equal-load) ranges from a sample — beyond-paper.

    Splitters are the sample quantiles; degenerate duplicate splitters (heavy
    skew) are de-duplicated by widening to the next representable key, so the
    ranges remain strictly increasing and cover [0, max_value].
    """
    if num_segments <= 0:
        raise ValueError("num_segments must be positive")
    if num_segments > max_value + 1:
        raise ValueError(
            f"more segments ({num_segments}) than domain values ({max_value + 1})"
        )
    need = num_segments - 1
    sample = np.asarray(sample)
    # An empty sample (an empty stream) has no quantiles: every splitter
    # then comes from the even padding below.
    qs = (
        np.quantile(sample, np.linspace(0, 1, num_segments + 1)[1:-1])
        if sample.size
        else np.zeros(0)
    )
    splits = np.unique(np.floor(qs).astype(np.int64))
    # Strictly increasing interior splitters within (0, max_value+1).
    splits = splits[(splits > 0) & (splits <= max_value)][:need]
    # Pad back to exactly num_segments-1 splitters by spreading the leftover
    # width.  A cheap evenly-spaced candidate pool suffices when the domain is
    # much larger than the deficit; materializing the full domain is the
    # fallback (only reachable when the domain is small, so it stays cheap).
    missing = need - len(splits)
    if missing > 0:
        pool = np.setdiff1d(
            np.unique(np.linspace(1, max_value, min(max_value, 4 * need)).astype(np.int64)),
            splits,
        )
        if pool.size < missing:
            pool = np.setdiff1d(np.arange(1, max_value + 1, dtype=np.int64), splits)
        # Evenly-spread distinct picks: floor(i * |pool| / missing) is
        # strictly increasing because |pool| >= missing (feasibility guard).
        take = (np.arange(missing) * pool.size) // missing
        splits = np.sort(np.concatenate([splits, pool[take]]))
    lo = np.concatenate([[0], splits])
    hi = np.concatenate([splits, [max_value + 1]])
    out = np.stack([lo, hi], axis=1).astype(np.int64)
    assert out.shape == (num_segments, 2)
    return out
