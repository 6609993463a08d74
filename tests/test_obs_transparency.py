"""Observability must be invisible: tracing on == tracing off, byte for byte.

The ISSUE 6 contract is that the whole observability plane — recording
tracer, metrics registry, even the in-band INT columns stamped onto the
wire — changes *nothing* about what the pipeline computes: the delivered
wire, the sorted output, the pass counts, the epoch count.  This suite runs
every scenario × topology × engine × pool-size cell twice, once with the
default null tracer and once fully instrumented, and diffs the results.

Hypothesis drives the randomized sweep when installed; on a bare
interpreter the ``tests/_hypstub.py`` path turns those into skips while the
deterministic twins — including the degenerate streams (empty, single key,
all-duplicate) and the jitter/arena/sampled corners — keep running.
"""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypstub import given, settings, st

from repro.data import SCENARIOS, TRACES, scenario_max_value, trace_max_value
from repro.net import run_pipeline
from repro.obs import MetricsRegistry, Tracer

TOPO_CASES = [
    ("single", {}),
    ("leaf_spine", {"num_leaves": 3}),
    ("tree", {"branching": 2, "height": 2}),
]
SEGS, LENGTH = 8, 16
WORKLOADS = sorted(TRACES) + sorted(SCENARIOS)


def _maxv(workload: str) -> int:
    return (
        trace_max_value(workload)
        if workload in TRACES
        else scenario_max_value(workload)
    )


def _gen(workload: str, n: int, seed: int = 0) -> np.ndarray:
    gen = TRACES.get(workload) or SCENARIOS[workload]
    return gen(n, seed=seed)


def _run(vals, maxv, topo, topo_kw, tracer=None, **over):
    kw = dict(
        topology=topo,
        num_segments=SEGS,
        segment_length=LENGTH,
        max_value=maxv,
        num_flows=4,
        payload_size=32,
        verify=True,
        seed=0,
    )
    kw.update(topo_kw)
    kw.update(over)
    return run_pipeline(vals, tracer=tracer, **kw)


def _assert_transparent(vals, maxv, topo, topo_kw, **over):
    """Instrumented run == uninstrumented run on every result field that
    describes the computation (telemetry itself is of course new)."""
    ref = _run(vals, maxv, topo, topo_kw, **over)
    tr = Tracer()
    # int_telemetry only where the fused engine runs (the default)
    int_ok = over.get("engine", "fused") == "fused"
    got = _run(vals, maxv, topo, topo_kw, tracer=tr,
               metrics=MetricsRegistry(), int_telemetry=int_ok, **over)
    np.testing.assert_array_equal(ref.output, got.output)
    np.testing.assert_array_equal(ref.delivered.values, got.delivered.values)
    np.testing.assert_array_equal(
        ref.delivered.segment_id, got.delivered.segment_id
    )
    np.testing.assert_array_equal(ref.delivered.seq, got.delivered.seq)
    assert ref.passes == got.passes
    assert ref.num_epochs == got.num_epochs
    assert ref.max_reorder_depth == got.max_reorder_depth
    assert ref.telemetry is None and got.telemetry is not None
    if int_ok and len(vals):
        assert got.delivered.int_meta is not None
    np.testing.assert_array_equal(got.output, np.sort(vals))
    return tr


@settings(max_examples=20, deadline=None)
@given(
    workload=st.sampled_from(WORKLOADS),
    topo_i=st.integers(min_value=0, max_value=len(TOPO_CASES) - 1),
    engine=st.sampled_from(["fused", "segment"]),
    num_servers=st.sampled_from([1, 2, 4]),
    range_mode=st.sampled_from(["static", "oracle", "sampled"]),
    n=st.integers(min_value=0, max_value=3000),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_tracing_is_transparent_property(
    workload, topo_i, engine, num_servers, range_mode, n, seed
):
    topo, topo_kw = TOPO_CASES[topo_i]
    vals = _gen(workload, n, seed=seed)
    _assert_transparent(
        vals, _maxv(workload), topo, topo_kw,
        engine=engine, num_servers=num_servers, range_mode=range_mode,
    )


# -- deterministic twins (always run, hypothesis or not) ----------------


@pytest.mark.parametrize("topo,topo_kw", TOPO_CASES)
@pytest.mark.parametrize("workload", ("random", "network"))
def test_tracing_is_transparent_across_topologies(workload, topo, topo_kw):
    vals = _gen(workload, 4000, seed=3)
    tr = _assert_transparent(vals, _maxv(workload), topo, topo_kw)
    assert tr.find(cat="hop")  # the fabric actually traced


@pytest.mark.parametrize("engine", ["fused", "segment", "faithful"])
def test_tracing_is_transparent_per_engine(engine):
    n = 2000 if engine != "faithful" else 400  # faithful is element-wise
    vals = _gen("random", n, seed=5)
    _assert_transparent(vals, _maxv("random"), "single", {}, engine=engine)


@pytest.mark.parametrize("num_servers", [1, 2, 4])
def test_tracing_is_transparent_per_pool_size(num_servers):
    vals = _gen("memory", 4000, seed=7)
    _assert_transparent(
        vals, _maxv("memory"), "leaf_spine", {"num_leaves": 3},
        num_servers=num_servers, range_mode="oracle",
    )


@pytest.mark.parametrize(
    "vals",
    [
        np.array([], dtype=np.int64),
        np.array([42], dtype=np.int64),
        np.full(500, 7, dtype=np.int64),
    ],
    ids=["empty", "single", "all_dupes"],
)
def test_tracing_is_transparent_on_degenerate_streams(vals):
    _assert_transparent(vals, 1 << 10, "single", {})


def test_tracing_is_transparent_under_jitter_sampling_and_arena():
    vals = _gen("drifting", 6000, seed=9)
    _assert_transparent(
        vals, _maxv("drifting"), "leaf_spine", {"num_leaves": 3},
        range_mode="sampled", jitter_window=8, reorder_capacity=64,
        num_servers=2, merge_backend="arena",
    )
