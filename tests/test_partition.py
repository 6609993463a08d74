"""Range partitioner properties: paper equal-width + beyond-paper quantile."""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # bare interpreter: property tests skip, the rest run
    from _hypstub import given, settings, st

from repro.core import quantile_ranges, segment_of, set_ranges


@given(st.integers(1, 64), st.integers(64, 100_000))
@settings(max_examples=100, deadline=None)
def test_set_ranges_partition_properties(segs, maxv):
    r = set_ranges(maxv, segs)
    assert r.shape == (segs, 2)
    # contiguous, non-overlapping, complete cover of [0, maxv]
    assert r[0, 0] == 0 and r[-1, 1] == maxv + 1
    np.testing.assert_array_equal(r[1:, 0], r[:-1, 1])
    widths = r[:, 1] - r[:, 0]
    # paper Alg.2: widths differ by at most 1, larger ones first
    assert widths.max() - widths.min() <= 1
    assert (np.diff(widths) <= 0).all()


@given(
    st.lists(st.integers(0, 10_000), min_size=16, max_size=2000),
    st.integers(2, 16),
)
@settings(max_examples=60, deadline=None)
def test_quantile_ranges_balanced_cover(sample, segs):
    sample = np.asarray(sample)
    maxv = 10_000
    r = quantile_ranges(sample, segs, maxv)
    assert r[0, 0] == 0 and r[-1, 1] == maxv + 1
    np.testing.assert_array_equal(r[1:, 0], r[:-1, 1])
    # every value routes to exactly one segment
    seg = segment_of(sample, r)
    assert ((seg >= 0) & (seg < len(r))).all()


def test_quantile_ranges_balance_skewed():
    """On a heavily skewed trace, quantile ranges balance load far better
    than the paper's equal-width ranges (the motivation for the beyond-
    paper splitters in core.distributed)."""
    rng = np.random.default_rng(0)
    vals = rng.zipf(1.5, size=100_000).clip(0, 10_000)
    S = 16
    eq = set_ranges(10_000, S)
    qr = quantile_ranges(vals, S, 10_000)
    eq_counts = np.bincount(segment_of(vals, eq), minlength=S)
    qr_counts = np.bincount(segment_of(vals, qr), minlength=S)
    # a single key holds ~38% of zipf(1.5) mass — that's the floor for any
    # contiguous-range scheme; quantile ranges get within ~1.05x of it,
    # equal-width ranges are 2.5x worse
    heaviest = np.bincount(vals).max()
    assert qr_counts.max() < eq_counts.max() / 2
    assert qr_counts.max() <= 1.1 * heaviest


def test_quantile_ranges_exact_count_on_memory_trace():
    """Regression: the splitter re-padding path must always return exactly
    ``num_segments`` ranges, even when heavy skew deduplicates most
    quantiles.  The memory trace (368 distinct power-of-two-ish IO sizes,
    Zipf popularity) is the paper trace that exercises this."""
    from repro.data import memory_trace, trace_max_value

    trace = memory_trace(50_000)
    maxv = trace_max_value("memory")
    for S in (16, 64, 256, 1024):
        r = quantile_ranges(trace, S, maxv)
        assert r.shape == (S, 2)
        assert r[0, 0] == 0 and r[-1, 1] == maxv + 1
        np.testing.assert_array_equal(r[1:, 0], r[:-1, 1])
        assert (r[:, 1] > r[:, 0]).all()
    # quantized to block counts the domain shrinks to 368 values; segment
    # counts right up to the domain boundary must still return exactly S
    blocks = trace // 512
    for S in (256, 368, 369):
        r = quantile_ranges(blocks, S, 368)
        assert r.shape == (S, 2)
        assert (r[:, 1] > r[:, 0]).all()


def test_quantile_ranges_degenerate_sample_exact_count():
    """A fully-degenerate sample (one distinct value) collapses every
    quantile; padding must still restore exactly num_segments ranges."""
    sample = np.full(1000, 7, dtype=np.int64)
    for maxv, S in [(20, 16), (20, 21), (10_000, 64)]:
        r = quantile_ranges(sample, S, maxv)
        assert r.shape == (S, 2)
        assert (r[:, 1] > r[:, 0]).all()
        seg = segment_of(sample, r)
        assert ((seg >= 0) & (seg < S)).all()


def test_quantile_ranges_empty_sample():
    """An empty stream (oracle ranges over no keys) still gets exactly
    num_segments strictly increasing ranges covering the domain."""
    for maxv, S in [(20, 16), (10_000, 64)]:
        r = quantile_ranges(np.zeros(0, dtype=np.int64), S, maxv)
        assert r.shape == (S, 2)
        assert (r[:, 1] > r[:, 0]).all()
        assert r[0, 0] == 0 and r[-1, 1] == maxv + 1
        np.testing.assert_array_equal(r[1:, 0], r[:-1, 1])


def test_quantile_ranges_infeasible_raises():
    """More segments than domain values used to silently return fewer than
    num_segments ranges; now it raises like set_ranges does."""
    with pytest.raises(ValueError, match="more segments"):
        quantile_ranges(np.asarray([1, 2, 3]), 12, 10)
