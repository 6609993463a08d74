"""End-to-end behaviour tests for the paper's system.

Full pipeline on each trace family: stream -> switch (MergeMarathon) ->
server (k-way natural merge sort per segment + concatenation) -> verified
sorted output, with the paper's headline effect (fewer merge passes, lower
server work) asserted — not just timed.
"""

import numpy as np
import pytest

from repro.core import (
    RunStats,
    Switch,
    marathon_streams,
    merge_passes,
    merge_sort,
    run_starts,
    server_sort,
)
from repro.data import TRACES, trace_max_value


@pytest.mark.parametrize("trace_name", ["random", "network", "memory"])
@pytest.mark.parametrize("n", [0, 1])
def test_trace_generators_take_tiny_n(trace_name, n):
    trace = TRACES[trace_name](n, seed=0)
    assert trace.shape == (n,)
    assert ((0 <= trace) & (trace <= trace_max_value(trace_name))).all()


@pytest.mark.parametrize("trace_name", ["random", "network", "memory"])
def test_full_pipeline_per_trace(trace_name):
    trace = TRACES[trace_name](100_000)
    maxv = trace_max_value(trace_name)

    _, base_passes = merge_sort(trace, k=10)

    streams, ranges = marathon_streams(trace, 16, 32, maxv)
    out, passes = server_sort(streams, k=10)
    np.testing.assert_array_equal(out, np.sort(trace))

    # the paper's effect: every segment needs fewer passes than the raw
    # stream, because runs are >= 32 long and segments are 16x shorter
    assert max(passes) < base_passes
    # and the pass count obeys the paper's model per segment
    for sub, p in zip(streams, passes):
        if sub.size:
            assert p == merge_passes(run_starts(sub).size, 10)


def test_switch_hardware_faithfulness_end_to_end():
    """The actual per-packet switch (not the vectorized model) feeding the
    server produces the correct global sort."""
    rng = np.random.default_rng(0)
    trace = rng.integers(0, 1000, size=3000)
    sw = Switch(number_of_segments=4, segment_length=8, max_value=999)
    vals, sids = sw.apply(trace)
    streams = [vals[sids == s] for s in range(4)]
    out, _ = server_sort(streams, k=10)
    np.testing.assert_array_equal(out, np.sort(trace))


def test_run_length_guarantee_drives_passes():
    """Longer pipelines (more stages) -> longer runs -> fewer passes,
    monotonically — Fig. 12-14's y-axis trend at the pass-count level."""
    rng = np.random.default_rng(1)
    trace = rng.integers(0, 32768, size=200_000)
    prev_passes = None
    for y in (4, 16, 64):
        streams, _ = marathon_streams(trace, 1, y, 32767)
        stats = RunStats.of(streams[0])
        assert stats.mean_len >= y * 0.9
        _, p = merge_sort(streams[0], k=10)
        if prev_passes is not None:
            assert p <= prev_passes
        prev_passes = p
