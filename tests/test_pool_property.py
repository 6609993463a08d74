"""Differential property suite for the sharded egress ServerPool (ISSUE 4).

The claim under test is the paper's scale sentence — "sort each range
separately and then concatenate": for every scenario × topology × engine ×
range mode × pool size, draining the fabric into ``S`` segment-affinity
streaming servers plus a distributed merge is **byte-identical** to the
single-server pipeline and to ``np.sort(input)``.

Hypothesis drives the randomized sweep when installed (strategies over the
full cross product); on a bare interpreter the ``tests/_hypstub.py`` path
turns those into skips while the deterministic twins below — including the
degenerate streams (empty, single key, all duplicates) and the shard_map
distributed-merge parity — keep running.
"""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:
    from _hypstub import given, settings, st

from repro.core.distributed import pool_concat
from repro.data import SCENARIOS, TRACES, scenario_max_value, trace_max_value
from repro.net import (
    AdaptiveControlPlane,
    ServerPool,
    run_pipeline,
    segment_affinity,
)

TOPO_CASES = [
    ("single", {}),
    ("leaf_spine", {"num_leaves": 3}),
    ("tree", {"branching": 2, "height": 2}),
]
POOL_SIZES = (1, 2, 4)
SEGS, LENGTH = 8, 16


def _run(vals, maxv, topo, topo_kw, mode, num_servers, **over):
    kw = dict(
        topology=topo,
        num_segments=SEGS,
        segment_length=LENGTH,
        max_value=maxv,
        num_flows=4,
        payload_size=32,
        range_mode=mode,
        num_servers=num_servers,
        verify=True,
    )
    kw.update(topo_kw)
    kw.update(over)
    return run_pipeline(vals, **kw)


def _assert_pool_matches_single(vals, maxv, topo, topo_kw, mode, S, **over):
    got = _run(vals, maxv, topo, topo_kw, mode, S, **over)
    ref = _run(vals, maxv, topo, topo_kw, mode, 1, **over)
    np.testing.assert_array_equal(got.output, np.sort(vals))
    np.testing.assert_array_equal(got.output, ref.output)
    assert got.passes == ref.passes
    assert got.max_reorder_depth == ref.max_reorder_depth
    assert got.num_servers == S and len(got.server_keys) == S
    assert sum(got.server_keys) == vals.size
    return got


# -- hypothesis sweep (skips without hypothesis) -------------------------


@settings(max_examples=25, deadline=None)
@given(
    scenario=st.sampled_from(sorted(SCENARIOS)),
    case=st.integers(min_value=0, max_value=len(TOPO_CASES) - 1),
    engine=st.sampled_from(("fused", "segment", "faithful")),
    mode=st.sampled_from(("static", "oracle", "sampled")),
    num_servers=st.sampled_from(POOL_SIZES),
    n=st.integers(min_value=1, max_value=400),
    seed=st.integers(min_value=0, max_value=2**16),
)
def test_pool_differential_scenario_matrix(
    scenario, case, engine, mode, num_servers, n, seed
):
    """Pool output == np.sort == single-server pipeline, plus identical
    passes, across the whole strategy space."""
    topo, topo_kw = TOPO_CASES[case]
    vals = SCENARIOS[scenario](n, seed=seed)
    maxv = scenario_max_value(scenario)
    _assert_pool_matches_single(
        vals, maxv, topo, topo_kw, mode, num_servers, engine=engine
    )


# -- deterministic twins -------------------------------------------------


@pytest.mark.parametrize("num_servers", POOL_SIZES)
@pytest.mark.parametrize("scenario", sorted(SCENARIOS))
def test_pool_matches_single_server_on_scenarios(scenario, num_servers):
    vals = SCENARIOS[scenario](600, seed=13)
    _assert_pool_matches_single(
        vals, scenario_max_value(scenario), "leaf_spine", {"num_leaves": 3},
        "sampled", num_servers,
    )


@pytest.mark.parametrize("num_servers", POOL_SIZES)
@pytest.mark.parametrize("mode", ("static", "sampled"))
def test_pool_empty_stream(mode, num_servers):
    # max_value pinned: an empty stream has no keys to derive a domain from
    # (and "oracle" needs data, so it is exercised from n=1 up instead).
    res = run_pipeline(
        np.zeros(0, dtype=np.int64),
        num_segments=SEGS,
        max_value=63,
        range_mode=mode,
        num_servers=num_servers,
        verify=True,
    )
    assert res.output.size == 0
    assert res.passes == [0] * SEGS
    assert res.server_keys == [0] * num_servers
    assert res.server_imbalance == 1.0


@pytest.mark.parametrize("num_servers", POOL_SIZES)
@pytest.mark.parametrize("mode", ("static", "oracle", "sampled"))
def test_pool_single_key_stream(mode, num_servers):
    got = _assert_pool_matches_single(
        np.array([37], dtype=np.int64), 63, "single", {}, mode, num_servers
    )
    np.testing.assert_array_equal(got.output, [37])


@pytest.mark.parametrize("num_servers", POOL_SIZES)
@pytest.mark.parametrize("mode", ("static", "oracle", "sampled"))
def test_pool_all_duplicate_stream(mode, num_servers):
    """Every key equal: one segment (and so one server) takes the whole
    stream — peak imbalance, still byte-identical output."""
    vals = np.full(500, 9, dtype=np.int64)
    got = _assert_pool_matches_single(
        vals, 63, "single", {}, mode, num_servers
    )
    if num_servers > 1:
        assert got.server_imbalance == pytest.approx(num_servers)


# -- affinity map --------------------------------------------------------


def test_segment_affinity_contiguous_balanced_blocks():
    for segs, S in [(8, 1), (8, 2), (8, 4), (16, 3), (7, 7)]:
        aff = segment_affinity(segs, S)
        assert aff.shape == (segs,)
        assert np.all(np.diff(aff) >= 0)  # server order == key-range order
        counts = np.bincount(aff, minlength=S)
        assert counts.min() >= 1  # no idle server
        assert counts.max() - counts.min() <= 1  # balanced blocks


def test_segment_affinity_rejects_bad_pool_sizes():
    with pytest.raises(ValueError, match="positive"):
        segment_affinity(8, 0)
    with pytest.raises(ValueError, match="exceeds"):
        segment_affinity(4, 8)


def test_pool_rejects_bad_affinity():
    with pytest.raises(ValueError, match="length"):
        ServerPool(8, 2, affinity=np.zeros(5, dtype=np.int64))
    with pytest.raises(ValueError, match="non-decreasing"):
        ServerPool(8, 2, affinity=np.array([1, 1, 1, 1, 0, 0, 0, 0]))
    with pytest.raises(ValueError, match="non-decreasing"):
        ServerPool(8, 2, affinity=np.array([0, 0, 0, 0, 1, 1, 1, 9]))


def test_pool_as_wide_as_segments_imbalance_over_owners():
    """S == num_segments: one segment per server, the affinity's edge.
    server_imbalance must equal the per-segment peak-over-mean (computed
    over the 8 owners), and the sort stays byte-identical."""
    vals = SCENARIOS["drifting"](2000, seed=17)
    got = _assert_pool_matches_single(
        vals, scenario_max_value("drifting"), "single", {}, "static", SEGS
    )
    keys = got.server_keys
    want = max(keys) / (sum(keys) / len(keys))
    assert got.server_imbalance == pytest.approx(want)


def test_pool_wider_than_segments_rejected_end_to_end():
    """More servers than segments cannot be sharded contiguously — the
    pipeline must refuse at construction (the segment_affinity guard),
    not silently leave servers idle."""
    with pytest.raises(ValueError, match="exceeds"):
        run_pipeline(
            np.arange(100),
            num_segments=4,
            segment_length=8,
            num_servers=8,
        )


def test_pool_imbalance_counts_only_owning_servers():
    """An explicit affinity that leaves servers idle (the epoch-sliced
    shape) must not deflate the mean: peak-over-mean is taken over the
    servers that own segments, so a perfectly even two-owner split reports
    ~1.0 — not the ~2.0 a divide-by-num_servers would produce."""
    vals = TRACES["network"](3000, seed=9)
    res = run_pipeline(
        vals,
        num_segments=SEGS,
        segment_length=LENGTH,
        max_value=trace_max_value("network"),
        num_flows=4,
        payload_size=32,
    )
    affinity = np.repeat([0, 3], SEGS // 2)  # servers 1 and 2 idle
    pool = ServerPool(SEGS, 4, affinity=affinity)
    pool.ingest_batch(res.delivered)
    out, _ = pool.finish()
    np.testing.assert_array_equal(out, np.sort(vals))
    keys = pool.server_keys
    assert keys[1] == keys[2] == 0
    owners = [keys[0], keys[3]]
    want = max(owners) / (sum(owners) / 2)
    assert pool.server_imbalance == pytest.approx(want)
    assert pool.server_imbalance < 2.0  # the deflated figure's floor


def test_control_plane_pool_affinity_tiles_per_epoch():
    """Epoch handoff re-shards virtual ids onto the same affinity blocks."""
    plane = AdaptiveControlPlane(SEGS, 63, warmup=8, max_epochs=3)
    plane.bootstrap_ranges()
    base = segment_affinity(SEGS, 2)
    np.testing.assert_array_equal(plane.pool_affinity(2), base)
    plane.install(plane.propose())
    plane.install(plane.propose())
    aff = plane.pool_affinity(2)
    assert aff.size == 3 * SEGS
    np.testing.assert_array_equal(aff, np.tile(base, 3))


# -- distributed merge ---------------------------------------------------


def _disjoint_shards(num, rng_seed=0):
    rng = np.random.default_rng(rng_seed)
    return [
        np.sort(rng.integers(0, 100, size=rng.integers(0, 60))) + 1000 * i
        for i in range(num)
    ]


def test_pool_concat_numpy_disjoint_and_overlapping():
    outs = _disjoint_shards(4)
    np.testing.assert_array_equal(
        pool_concat(outs, disjoint=True), np.concatenate(outs)
    )
    # overlapping shards (epoched ranges): k-way merge, still sorted
    overlapping = [np.sort(o % 97) for o in outs]
    got = pool_concat(overlapping, disjoint=False)
    np.testing.assert_array_equal(got, np.sort(np.concatenate(overlapping)))
    assert pool_concat([], disjoint=True).size == 0


def test_pool_concat_shard_map_matches_numpy():
    """backend="shard_map" is byte-identical to the numpy path — through
    the collective on whatever devices are present (one on the plain CPU
    backend, four under the forced host device count)."""
    outs = _disjoint_shards(4, rng_seed=7)
    np.testing.assert_array_equal(
        pool_concat(outs, disjoint=True, backend="shard_map"),
        np.concatenate(outs),
    )


@pytest.mark.parametrize("base", [0, 1 << 40], ids=["narrow", "int64"])
def test_pool_concat_sharded_collective_path(base):
    """The mesh spans the largest device count dividing S — all four
    servers on one device here, one server per device under
    XLA_FLAGS=--xla_force_host_platform_device_count=4 (scripts/ci.sh).
    Keys beyond int32 cross the device boundary untruncated."""
    jax = pytest.importorskip("jax")
    from repro.core.distributed import pool_concat_sharded
    from repro.distributed.sharding import pool_mesh

    mesh = pool_mesh(4)
    assert 4 % mesh.size == 0 and mesh.size <= jax.device_count()
    if jax.device_count() >= 4:
        assert mesh.size == 4
    outs = [o + base for o in _disjoint_shards(4, rng_seed=11)]
    outs[1] = outs[1][:0]  # ragged + empty shard survive the padding
    np.testing.assert_array_equal(
        pool_concat_sharded(outs, mesh), np.concatenate(outs)
    )


@pytest.mark.parametrize("mode", ("static", "sampled"))
def test_pipeline_shard_map_backend_matches_numpy_backend(mode):
    vals = TRACES["network"](1500, seed=17)
    maxv = trace_max_value("network")
    a = _run(vals, maxv, "single", {}, mode, 4, pool_backend="shard_map")
    b = _run(vals, maxv, "single", {}, mode, 4, pool_backend="numpy")
    np.testing.assert_array_equal(a.output, b.output)
    assert a.passes == b.passes


# -- scaling (the benchmark's tier-1 twin) -------------------------------


@pytest.mark.slow
def test_pool_makespan_s4_beats_s1():
    """The scale claim, timed: 4 range-sharded servers drain the stream
    faster (makespan: slowest server + distributed merge) than one.  The
    full 1M-key acceptance run lives in benchmarks/net_bench.py
    `server_scaling` (gated in scripts/ci.sh); this twin uses 400k keys."""
    vals = TRACES["random"](400_000, seed=3)
    maxv = trace_max_value("random")

    def makespan(S):
        return min(
            run_pipeline(
                vals,
                topology="single",
                num_segments=16,
                segment_length=64,
                max_value=maxv,
                payload_size=256,
                num_flows=8,
                range_mode="oracle",
                num_servers=S,
            ).server_seconds
            for _ in range(3)
        )

    assert makespan(4) < makespan(1)
