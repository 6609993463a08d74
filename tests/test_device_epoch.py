"""The whole-epoch compiled device engine: identity, residency, payloads.

The load-bearing claims (ISSUE tentpole):

1. ``engine="device"`` is byte-identical to the fused / segment / faithful
   engines — all four wire columns, per-hop stats, and server pass counts —
   across scenario × topology × pool size.
2. The epoch is device-resident: exactly one host→device transfer (the
   ingress columns) and one device→host transfer (the egress fetch) per
   epoch, counted at the ``device_put``/``device_get`` choke points.
3. Payload records ride as packed key+row-index 64-bit columns and the
   payload itself is gathered exactly once at egress: ``sorted_payload``
   equals ``payload[np.argsort(values, kind="stable")]``.
4. Engines without per-key provenance (segment, faithful) *reject* payload
   rows rather than silently dropping them.
"""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st
except ImportError:  # bare interpreter: property tests skip, the rest run
    from _hypstub import given, settings, st

from repro.data.scenarios import SCENARIOS, scenario_max_value
from repro.net import (
    DeviceDelivery,
    HopSpec,
    WireBatch,
    interleave_batch,
    leaf_spine_graph,
    run_graph,
    run_pipeline,
    split_flows,
    tree_graph,
)
from repro.net.device_epoch import (
    _COMPARE_ALL_MAX,
    _SLICED_SEGMENTS_MAX,
    TRANSFER_COUNTS,
    device_self_check,
    reset_transfer_counts,
)
from repro.net.engine import run_hop

TOPO_CASES = [
    ("single", {}),
    ("leaf_spine", {"num_leaves": 4}),
    ("tree", {"branching": 2, "height": 3}),
]
N = 3000
SEGS, LENGTH = 8, 16


def _common(scenario, **over):
    kw = dict(
        num_segments=SEGS,
        segment_length=LENGTH,
        max_value=scenario_max_value(scenario),
        num_flows=4,
        payload_size=32,
    )
    kw.update(over)
    return kw


def _assert_batches_equal(a, b, msg=""):
    for col in ("values", "flow_id", "seq", "segment_id"):
        np.testing.assert_array_equal(
            getattr(a, col), getattr(b, col), err_msg=f"{msg}:{col}"
        )


# -- four-way engine identity -------------------------------------------


@pytest.mark.parametrize("scenario", ["adversarial_skew", "drifting"])
@pytest.mark.parametrize("topo,topo_kw", TOPO_CASES)
@pytest.mark.parametrize("num_servers", [1, 4])
def test_four_way_engine_identity(scenario, topo, topo_kw, num_servers):
    vals = SCENARIOS[scenario](N, seed=7)
    kw = _common(scenario, num_servers=num_servers, verify=True)
    results = {
        eng: run_pipeline(vals, topology=topo, engine=eng, **kw, **topo_kw)
        for eng in ("faithful", "segment", "fused", "device")
    }
    ref = results["faithful"]
    for eng, res in results.items():
        np.testing.assert_array_equal(res.output, ref.output, err_msg=eng)
        assert res.passes == ref.passes, eng
        _assert_batches_equal(res.delivered, ref.delivered, eng)
        assert len(res.hop_stats) == len(ref.hop_stats)
        for sd, sf in zip(res.hop_stats, ref.hop_stats):
            assert sd == sf  # frozen dataclass: every scalar stat
            np.testing.assert_array_equal(sd.segment_loads, sf.segment_loads)


@pytest.mark.parametrize("range_mode", ["oracle", "sampled"])
def test_device_matches_fused_across_range_modes(range_mode):
    vals = SCENARIOS["drifting"](N, seed=3)
    kw = _common("drifting", range_mode=range_mode, verify=True)
    rd = run_pipeline(vals, topology="leaf_spine", num_leaves=4, engine="device", **kw)
    rf = run_pipeline(vals, topology="leaf_spine", num_leaves=4, engine="fused", **kw)
    np.testing.assert_array_equal(rd.output, rf.output)
    assert rd.passes == rf.passes
    assert rd.num_epochs == rf.num_epochs
    _assert_batches_equal(rd.delivered, rf.delivered)


# -- device residency: one transfer each way ----------------------------


def test_one_transfer_each_way_per_epoch():
    vals = SCENARIOS["adversarial_skew"](N, seed=1)
    graph = tree_graph(2, 3)
    flows = split_flows(vals, 4, 32)
    batch = interleave_batch(flows, "round_robin", seed=0)
    spec = HopSpec(SEGS, LENGTH, max_value=scenario_max_value("adversarial_skew"))
    reset_transfer_counts()
    out, stats = run_graph(graph, batch, spec, engine="device")
    assert TRANSFER_COUNTS == {"to_device": 1, "to_host": 1}
    assert isinstance(out, DeviceDelivery)
    # The grouped columns degrade to a plain WireBatch on any mutation, so
    # downstream consumers that slice or reorder never see stale groupings.
    assert out.take(np.arange(out.values.size)).__class__ is WireBatch
    ref, _ = run_graph(graph, batch, spec, engine="fused")
    _assert_batches_equal(out, ref)


def test_observed_mode_still_one_fetch(monkeypatch):
    from repro.net import device_epoch
    from repro.obs import MetricsRegistry, Tracer

    vals = SCENARIOS["drifting"](N, seed=5)
    flows = split_flows(vals, 4, 32)
    batch = interleave_batch(flows, "round_robin", seed=0)
    spec = HopSpec(SEGS, LENGTH, max_value=scenario_max_value("drifting"))
    graph = leaf_spine_graph(4)
    ref, rstats = run_graph(graph, batch, spec, engine="fused")
    monkeypatch.setattr(device_epoch, "_PROGRAM_CACHE", {})

    # A recording tracer alone runs the program no tracer runs: no taps,
    # no hop spans, one fetch.
    reset_transfer_counts()
    tr = Tracer()
    out, _ = run_graph(graph, batch, spec, engine="device", tracer=tr)
    assert TRANSFER_COUNTS == {"to_device": 1, "to_host": 1}
    assert not tr.find(cat="hop")
    assert [s.name for s in tr.find(cat="epoch")] == [
        "epoch:put", "epoch:build", "epoch:device", "epoch:fetch",
    ]
    (key,) = device_epoch._PROGRAM_CACHE
    assert key[-1] is False  # taps
    untraced, _ = run_graph(graph, batch, spec, engine="device")
    assert list(device_epoch._PROGRAM_CACHE) == [key]
    _assert_batches_equal(out, ref)
    _assert_batches_equal(untraced, ref)

    # A metrics registry still observes the run: the taps ride the same
    # single fetch.
    reset_transfer_counts()
    out, stats = run_graph(
        graph, batch, spec, engine="device", metrics=MetricsRegistry()
    )
    assert TRANSFER_COUNTS == {"to_device": 1, "to_host": 1}
    assert [k[-1] for k in device_epoch._PROGRAM_CACHE] == [False, True]
    _assert_batches_equal(out, ref)
    for sd, sf in zip(stats, rstats):
        np.testing.assert_array_equal(sd.ship_emission, sf.ship_emission)


def test_arena_tournaments_count_their_transfers():
    """Each run-arena tournament copies its matrix to the device and back
    through the counted choke points, and its span carries those bytes."""
    from repro.obs import Tracer

    vals = np.random.default_rng(4).integers(0, 1 << 15, size=40_000)
    tr = Tracer()
    reset_transfer_counts()
    res = run_pipeline(
        vals, engine="device", merge_backend="arena", tracer=tr,
        num_segments=4, segment_length=16, max_value=1 << 15, num_flows=1,
        payload_size=64,
    )
    np.testing.assert_array_equal(res.output, np.sort(vals))
    tours = [s for s in tr.spans if s.name.startswith("tournament:b")]
    assert tours
    assert TRANSFER_COUNTS == {
        "to_device": 1 + len(tours), "to_host": 1 + len(tours),
    }
    for sp in tours:
        B = int(sp.name.removeprefix("tournament:b"))
        rows = max(2, 1 << (sp.args["runs"] - 1).bit_length())
        assert sp.args["bytes"] == 2 * rows * B * 2  # uint16 keys


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
def test_epoch_program_names_its_stages():
    """Every hop, stage and uplink merge of the epoch is a named scope, so
    a profiler trace's ``tf_op`` of each device operation names it."""
    import jax

    from repro.core.partition import set_ranges
    from repro.kernels.ops import x64_scope
    from repro.net.device_epoch import _epoch_program

    max_value = scenario_max_value("drifting")
    spec = HopSpec(
        SEGS, LENGTH, max_value, set_ranges(max_value, SEGS), payload_size=32
    )
    graph = leaf_spine_graph(2)
    fn = _epoch_program(
        graph, spec, spec.ranges, (500, 500), False, False, None, False
    )
    arg = jax.ShapeDtypeStruct((500,), np.int64)
    with x64_scope():
        text = fn.lower((arg, arg), ()).as_text(debug_info=True)
    for hop in ("leaf0", "leaf1", "spine"):
        for stage in ("route", "rank", "block_sort", "emit", "ship"):
            assert f'"jit(epoch_fn)/hop_{hop}/{stage}/' in text, (hop, stage)
    assert '"jit(epoch_fn)/hop_spine/uplink_merge/' in text


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
@pytest.mark.parametrize(
    "topo,carry_rows",
    [("single", False), ("leaf_spine", False), ("single", True),
     ("single", "wide")],
    ids=["keys_single", "keys_leaf_spine", "records_packed", "wide_keys"],
)
def test_epoch_program_has_no_scatter(topo, carry_rows):
    """Ranks, counts, the block layout and the emission order lower without
    a scatter (the Pallas kernel is off: interpret-mode Pallas is plain HLO
    with scatters of its own); wide keys (10-byte keys as three sort words,
    the rows riding as the fourth) too."""
    import jax

    from repro.core.partition import set_ranges, set_ranges_wide
    from repro.kernels.ops import x64_scope
    from repro.net.device_epoch import _epoch_program
    from repro.net.topology import HopGraph, HopNode

    max_value = scenario_max_value("drifting")
    ranges = (
        set_ranges_wide(10, SEGS) if carry_rows == "wide"
        else set_ranges(max_value, SEGS)
    )
    spec = HopSpec(SEGS, LENGTH, max_value, ranges, payload_size=32)
    if topo == "single":
        graph, group_ns = HopGraph((HopNode("switch"),), num_groups=1), (1000,)
    else:
        graph, group_ns = leaf_spine_graph(2), (500, 500)
    fn = _epoch_program(
        graph, spec, spec.ranges, group_ns, bool(carry_rows), False, None,
        False,
    )
    if carry_rows == "wide":
        args = (
            tuple(jax.ShapeDtypeStruct((3, g), np.int32) for g in group_ns),
            tuple(jax.ShapeDtypeStruct((g,), np.int32) for g in group_ns),
        )
    else:
        keys = tuple(jax.ShapeDtypeStruct((g,), np.int64) for g in group_ns)
        args = (keys, keys if carry_rows else ())
    with x64_scope():
        text = fn.lower(*args).as_text()
    assert "stablehlo.sort" in text
    assert "stablehlo.scatter" not in text


def _segment_batch(counts, S, max_value, lo=0, seed=0):
    """Arrivals holding exactly ``counts[s]`` keys of segment ``s`` of the
    equal-width ranges over ``[lo, lo + max_value]``, in a shuffled order."""
    from repro.core.partition import set_ranges

    ranges = set_ranges(max_value, S) + lo
    rng = np.random.default_rng(seed)
    vals = np.concatenate([
        rng.integers(a, b, c) for (a, b), c in zip(ranges, counts)
    ]).astype(np.int64)
    rng.shuffle(vals)
    flows = split_flows(vals, 2, 32)
    return interleave_batch(flows, "round_robin", seed=seed), ranges


#: More segments than the sliced block layout takes, a fifth of them empty.
_MANY_SEGS = _SLICED_SEGMENTS_MAX + 8
_SKEWED_MANY = [(s * 7) % 13 if s % 5 else 0 for s in range(_MANY_SEGS)]
#: A block length whose 16 segments' first arrivals outnumber the table the
#: emission search compares with.
_SORT_SEARCH_L = 2 * _COMPARE_ALL_MAX // 16

_HOP_EDGES = {
    # name: (S, L, per-segment counts, domain (offset, max value), payload
    # rows carried)
    "one_segment": (8, 16, [0, 0, 0, 300, 0, 0, 0, 0], (0, 799), False),
    "empty_segments": (8, 16, [50, 0, 0, 37, 0, 120, 0, 9], (0, 799), False),
    "all_below_L": (8, 16, [3, 15, 1, 7, 0, 12, 15, 5], (0, 799), False),
    "exact_L_and_kL": (8, 16, [16, 32, 48, 16, 64, 160, 16, 32], (0, 799),
                       False),
    "n_below_L": (8, 64, [1, 2, 0, 3, 0, 0, 5, 1], (0, 799), False),
    "gathered_layout": (_MANY_SEGS, 4, _SKEWED_MANY, (0, 799), False),
    "records_exact_L_and_kL": (8, 16, [16, 32, 48, 16, 64, 160, 16, 32],
                               (0, 799), True),
    "records_gathered_layout": (_MANY_SEGS, 4, _SKEWED_MANY, (0, 799), True),
    "search_by_sort": (16, _SORT_SEARCH_L, [3000, 0, 2048, 100, 0, 4096, 7,
                                            0, 0, 0, 1, 2049, 0, 0, 0, 0],
                       (0, 1 << 16), False),
    "records_wide_keys": (8, 16, [50, 0, 0, 37, 16, 120, 0, 9],
                          (0, (1 << 61) - 1), True),
    "negative_domain": (8, 16, [50, 0, 0, 37, 16, 120, 0, 9], (-400, 799),
                        False),
}


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
@pytest.mark.parametrize("case", list(_HOP_EDGES))
def test_device_hop_edge_layouts(case, monkeypatch):
    """The scatter-free forms where the shift by L and the closed-form row
    lengths could slip — one crowded segment, empty segments, segments
    under L, exactly L and kL keys, fewer keys than L, more segments than
    the sliced layout takes, more first arrivals than the search compares,
    packed and wide records, a negative domain (stable argsorts in place of
    packed sorts) — each byte-identical to ``fused``."""
    from repro.kernels import ops
    from repro.net import device_epoch

    S, L, counts, (lo, max_value), carry_rows = _HOP_EDGES[case]
    batch, ranges = _segment_batch(counts, S, max_value, lo=lo)
    if carry_rows:
        batch = batch.with_row_index(np.arange(len(batch), dtype=np.int64))
    spec = HopSpec(S, L, max_value, ranges, payload_size=8)
    monkeypatch.setattr(device_epoch, "_PROGRAM_CACHE", {})
    monkeypatch.setattr(ops, "LOWERING_COUNTS", type(ops.LOWERING_COUNTS)())
    of, sf = run_hop(batch, spec, "sw", engine="fused")
    od, sd = run_hop(batch, spec, "sw", engine="device")
    assert dict(ops.LOWERING_COUNTS) == {"epoch_hop:scatter_free:int64": 1}
    _assert_batches_equal(od, of, case)
    if carry_rows:
        np.testing.assert_array_equal(od.row_index, of.row_index)
    np.testing.assert_array_equal(sd.ship_emission, sf.ship_emission)
    np.testing.assert_array_equal(sd.segment_loads, counts)
    assert sd == sf


@pytest.mark.filterwarnings("ignore:Some donated buffers were not usable")
@pytest.mark.parametrize(
    "topo,topo_kw,num_flows,segments",
    [
        ("leaf_spine", {"num_leaves": 2}, 1, SEGS),
        ("leaf_spine", {"num_leaves": 2}, 1, _MANY_SEGS),
        ("tree", {"branching": 2, "height": 3}, 3, SEGS),
    ],
    ids=["leaf_spine_one_flow", "leaf_spine_one_flow_gathered",
         "tree_fewer_flows_than_leaves"],
)
def test_device_epoch_with_an_idle_leaf(topo, topo_kw, num_flows, segments):
    """An ingress group with no flow gives its leaf a hop with no arrivals;
    the epoch still runs, byte-identical to ``fused``."""
    vals = SCENARIOS["drifting"](N, seed=5)
    kw = _common("drifting", num_flows=num_flows, num_segments=segments,
                 verify=True)
    rd = run_pipeline(vals, topology=topo, engine="device", **kw, **topo_kw)
    rf = run_pipeline(vals, topology=topo, engine="fused", **kw, **topo_kw)
    np.testing.assert_array_equal(rd.output, rf.output)
    assert rd.passes == rf.passes
    _assert_batches_equal(rd.delivered, rf.delivered)
    assert [s.arrivals for s in rd.hop_stats].count(0) >= 1
    for sd, sf in zip(rd.hop_stats, rf.hop_stats):
        assert sd == sf


# -- payload records ----------------------------------------------------


@pytest.mark.parametrize("engine", ["fused", "device"])
@pytest.mark.parametrize("merge_backend", ["numpy", "arena"])
def test_payload_gathered_once_at_egress(engine, merge_backend):
    vals = SCENARIOS["adversarial_skew"](N, seed=11)
    payload = (vals * 7 + 3).reshape(-1, 1).repeat(3, axis=1)
    payload[:, 1] = np.arange(vals.size)
    res = run_pipeline(
        vals,
        topology="tree",
        branching=2,
        height=3,
        engine=engine,
        payload=payload,
        merge_backend=merge_backend,
        num_servers=4,
        verify=True,
        **_common("adversarial_skew"),
    )
    order = np.argsort(vals, kind="stable")
    np.testing.assert_array_equal(res.payload_row_order, order)
    np.testing.assert_array_equal(res.sorted_payload, payload[order])
    np.testing.assert_array_equal(res.sorted_payload[:, 0], res.output * 7 + 3)


def test_payload_identity_fused_vs_device():
    vals = SCENARIOS["drifting"](N, seed=2)
    payload = np.arange(vals.size, dtype=np.int64)[:, None]
    kw = _common("drifting", payload=payload, verify=True)
    rd = run_pipeline(vals, topology="leaf_spine", num_leaves=4, engine="device", **kw)
    rf = run_pipeline(vals, topology="leaf_spine", num_leaves=4, engine="fused", **kw)
    np.testing.assert_array_equal(rd.sorted_payload, rf.sorted_payload)
    np.testing.assert_array_equal(rd.payload_row_order, rf.payload_row_order)
    np.testing.assert_array_equal(
        rd.delivered.row_index, rf.delivered.row_index
    )


@pytest.mark.parametrize("engine", ["segment", "faithful"])
def test_provenance_free_engines_reject_payload(engine):
    vals = SCENARIOS["adversarial_skew"](N, seed=0)
    payload = vals.reshape(-1, 1)
    with pytest.raises(ValueError, match="row indices"):
        run_pipeline(
            vals, engine=engine, payload=payload, **_common("adversarial_skew")
        )


def test_payload_domain_guard():
    vals = np.arange(100, dtype=np.int64)
    with pytest.raises(ValueError, match="63 bits"):
        run_pipeline(
            vals,
            payload=vals.reshape(-1, 1),
            num_segments=4,
            segment_length=8,
            max_value=1 << 60,
        )


# -- single-hop property sweep ------------------------------------------


@given(st.integers(0, 2**32 - 1), st.integers(1, 4), st.sampled_from([8, 16, 32]))
@settings(max_examples=25, deadline=None)
def test_device_hop_matches_fused_hop(seed, num_flows, length):
    rng = np.random.default_rng(seed)
    n = int(rng.integers(64, 1200))
    mv = int(rng.integers(100, 1 << 24))
    vals = rng.integers(0, mv + 1, n)
    flows = split_flows(vals, num_flows, 32)
    batch = interleave_batch(flows, "round_robin", seed=seed % 97)
    spec = HopSpec(SEGS, length, max_value=mv)
    of, sf = run_hop(batch, spec, "sw", engine="fused")
    od, sd = run_hop(batch, spec, "sw", engine="device")
    _assert_batches_equal(od, of)
    np.testing.assert_array_equal(sd.ship_emission, sf.ship_emission)
    assert sd == sf
    np.testing.assert_array_equal(sd.segment_loads, sf.segment_loads)


def test_device_hop_empty_batch():
    spec = HopSpec(SEGS, LENGTH, max_value=1000)
    empty = interleave_batch(split_flows(np.zeros(0, np.int64), 2, 32), "round_robin")
    out, stats = run_hop(empty, spec, "sw", engine="device")
    assert out.values.size == 0 and stats.arrivals == 0


# -- guard rails --------------------------------------------------------


def test_device_rejects_int_telemetry():
    vals = SCENARIOS["adversarial_skew"](512, seed=0)
    with pytest.raises(ValueError, match="telemetry"):
        run_pipeline(
            vals, engine="device", int_telemetry=True, **_common("adversarial_skew")
        )


def test_device_rejects_out_of_domain_values():
    spec = HopSpec(SEGS, LENGTH, max_value=100)
    batch = interleave_batch(
        split_flows(np.asarray([5, 500]), 1, 32), "round_robin"
    )
    with pytest.raises(ValueError, match="domain"):
        run_hop(batch, spec, "sw", engine="device")


def test_self_check_interpret():
    """The CI entry point: the Pallas block-sort kernel inside the compiled
    epoch, run in interpret mode, still produces the fused engine's bytes."""
    device_self_check(interpret=True, n=2048, seed=4)
