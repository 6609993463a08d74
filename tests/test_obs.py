"""Unit + integration tests for the observability plane (ISSUE 6).

Three layers under test: the span tracer (:mod:`repro.obs.trace` — nesting,
Chrome-trace export, the null tracer's zero-record contract and its role as
the repo's wall-clock source), the metric registry (:mod:`repro.obs.metrics`
— pow2 histogram bucketing, kind-collision detection, snapshot shape), and
the in-band telemetry columns (:mod:`repro.obs.telemetry` — stamping,
gather/concat propagation, depth discipline).  The integration half drives
:func:`repro.net.run_pipeline` with a recording tracer and asserts the span
hierarchy the docstrings promise actually shows up — every hop, the stages
inside it, the server merge levels — plus the egress-side INT summary and
the satellite fix: a fresh (degenerate) :class:`~repro.net.egress.ServerPool`
answers its observability accessors instead of raising.
"""

import json

import numpy as np
import pytest

from repro.net import ServerPool, run_pipeline
from repro.net.engine import HopSpec, run_hop
from repro.net.wire import WireBatch, concat_batches
from repro.obs import (
    IntColumns,
    MetricsRegistry,
    NULL_TRACER,
    NullTracer,
    Tracer,
    int_summary,
)

SEGS, LENGTH = 8, 16


# -- tracer ------------------------------------------------------------


def test_tracer_records_nested_spans_with_depth_and_args():
    tr = Tracer()
    with tr.span("outer", cat="hop", keys=10) as outer:
        with tr.span("inner", cat="stage"):
            pass
        outer.set(keys_out=9)
    # inner closes first (spans append on exit)
    inner, outer = tr.spans
    assert (inner.name, inner.depth) == ("inner", 1)
    assert (outer.name, outer.depth) == ("outer", 0)
    assert outer.args == {"keys": 10, "keys_out": 9}
    assert outer.dur >= inner.dur >= 0
    assert tr.find(cat="stage") == [inner]
    assert tr.total_seconds("outer") == outer.seconds


def test_tracer_lanes_nest_independently():
    tr = Tracer()
    with tr.span("a", tid=0):
        with tr.span("b", tid=3):  # different lane: depth restarts at 0
            pass
    b, a = tr.spans
    assert (a.tid, a.depth) == (0, 0)
    assert (b.tid, b.depth) == (3, 0)


def test_chrome_trace_export_is_valid_and_sorted(tmp_path):
    tr = Tracer()
    with tr.span("work", cat="hop", n=np.int64(4)):
        tr.instant("tick", cat="control", epoch=0)
    doc = tr.chrome_trace()
    assert set(doc) == {"traceEvents", "displayTimeUnit"}
    phases = [e["ph"] for e in doc["traceEvents"]]
    assert sorted(phases) == ["X", "i"]
    ts = [e["ts"] for e in doc["traceEvents"]]
    assert ts == sorted(ts)
    path = tmp_path / "trace.json"
    tr.dump(str(path))
    loaded = json.loads(path.read_text())  # numpy args serialized via fallback
    assert loaded["traceEvents"][0]["name"] in ("work", "tick")


def test_null_tracer_records_nothing_but_timed_still_measures():
    tr = NullTracer()
    assert tr is not NULL_TRACER and not tr.enabled
    with tr.span("x", cat="hop", keys=3) as sp:
        sp.set(anything=1)  # reaches the profiler annotation only
    assert sp.seconds == 0.0
    assert not hasattr(tr, "spans")  # keeps no record
    with tr.timed("wall") as t:
        sum(range(1000))
    assert t.seconds > 0  # the single wall-clock source keeps working
    tr.instant("evt")  # no-op, no storage to check


# -- metrics -----------------------------------------------------------


def test_histogram_pow2_buckets_scalar_and_vectorized_agree():
    values = [1, 2, 4, 4, 0, 1023, 7]
    h1 = MetricsRegistry().histogram("h")
    for v in values:
        h1.observe(v)
    h2 = MetricsRegistry().histogram("h")
    h2.observe_many(np.array(values))
    want = {0: 1, 1: 1, 2: 1, 3: 3, 10: 1}
    assert h1.snapshot()["buckets"] == want
    assert h2.snapshot() == h1.snapshot()
    assert h1.snapshot()["mean"] == pytest.approx(sum(values) / len(values))
    with pytest.raises(ValueError, match=">= 0"):
        h1.observe(-1)
    with pytest.raises(ValueError, match=">= 0"):
        h1.observe_many(np.array([3, -2]))


def test_registry_keys_by_label_and_rejects_kind_collisions():
    reg = MetricsRegistry()
    reg.counter("keys", "leaf0").inc(5)
    reg.counter("keys", "leaf0").inc(2)  # same instrument comes back
    reg.counter("keys", "spine").inc(1)
    reg.gauge("load").set(np.array([1, 2]))
    with pytest.raises(ValueError, match="already registered"):
        reg.gauge("keys", "leaf0")
    snap = reg.snapshot()
    assert snap["counters"]["keys"] == {"leaf0": 7, "spine": 1}
    assert snap["gauges"]["load"][""] == [1, 2]  # arrays become lists
    json.dumps(snap)  # snapshot must be JSON-able as-is


def test_gauge_high_water_keeps_the_max():
    g = MetricsRegistry().gauge("depth")
    for v in (3, 9, 4):
        g.high_water(v)
    assert g.snapshot() == 9


def test_series_decimates_but_keeps_endpoints_shape():
    from repro.obs import Series

    s = Series(max_points=8)
    for i in range(100):
        s.append(i, i * i)
    snap = s.snapshot()
    assert len(snap["x"]) < 100 and snap["stride"] > 1
    assert snap["x"] == sorted(snap["x"])  # order survives decimation


# -- INT columns -------------------------------------------------------


def test_int_columns_stamp_take_slice_concat_roundtrip():
    cols = IntColumns.empty(4).stamp(7, [1, 2, 3, 4], [10, 20, 30, 40])
    assert cols.depth == 1 and len(cols) == 4
    taken = cols.take(np.array([2, 0]))
    assert taken.queue_depth[:, 0].tolist() == [3, 1]
    sliced = cols.slice(1, 3)
    assert sliced.rank_ticks[:, 0].tolist() == [20, 30]
    back = IntColumns.concat([taken, sliced])
    assert len(back) == 4 and back.depth == 1
    assert back.hop_id[:, 0].tolist() == [7] * 4
    assert not cols.hop_id.flags.writeable  # frozen like the wire columns


def test_int_columns_concat_rejects_depth_mismatch():
    one = IntColumns.empty(2).stamp(0, [1, 1], [0, 0])
    two = one.stamp(1, [2, 2], [5, 5])
    with pytest.raises(ValueError, match="different hop depths"):
        IntColumns.concat([one, two])


def test_int_summary_aggregates_per_depth_and_hop():
    cols = IntColumns.empty(3).stamp(0, [4, 2, 6], [1, 2, 3]).stamp(
        5, [1, 1, 1], [7, 8, 9]
    )
    rows = int_summary(cols)
    assert [(r["depth"], r["hop_id"], r["keys"]) for r in rows] == [
        (0, 0, 3), (1, 5, 3)
    ]
    assert rows[0]["max_queue_depth"] == 6
    assert rows[1]["mean_rank_ticks"] == pytest.approx(8.0)
    assert int_summary(None) == [] and int_summary(IntColumns.empty(0)) == []


def test_wire_batch_carries_int_meta_through_take_and_concat():
    vals = np.arange(6, dtype=np.int64)
    z = np.zeros(6, dtype=np.int64)
    meta = IntColumns.empty(6).stamp(3, np.ones(6), vals)
    b = WireBatch(vals, z, z.copy(), z.copy()).with_int_meta(meta)
    assert b.take(np.array([4, 1])).int_meta.rank_ticks[:, 0].tolist() == [4, 1]
    cat = concat_batches([b.slice_keys(0, 2), b.slice_keys(2, 6)])
    assert cat.int_meta.rank_ticks[:, 0].tolist() == list(range(6))
    # mixing stamped and unstamped key-carrying batches drops the telemetry
    plain = WireBatch(vals, z, z.copy(), z.copy())
    assert concat_batches([b, plain]).int_meta is None
    with pytest.raises(ValueError, match="int_meta rows"):
        WireBatch(vals, z, z.copy(), z.copy(), int_meta=IntColumns.empty(2))


# -- pipeline integration ----------------------------------------------


def _run(vals, tracer=None, metrics=None, **over):
    kw = dict(
        topology="leaf_spine",
        num_leaves=3,
        num_segments=SEGS,
        segment_length=LENGTH,
        max_value=1 << 16,
        num_flows=4,
        payload_size=32,
        verify=True,
    )
    kw.update(over)
    if kw["topology"] != "leaf_spine":
        kw.pop("num_leaves", None)
    return run_pipeline(vals, tracer=tracer, metrics=metrics, **kw)


@pytest.fixture(scope="module")
def vals():
    return np.random.default_rng(11).integers(0, 1 << 16, size=6000)


def test_pipeline_emits_the_promised_span_hierarchy(vals):
    tr = Tracer()
    res = _run(vals, tracer=tr, int_telemetry=True)
    names = {s.name for s in tr.spans}
    for hop in ("hop:leaf0", "hop:leaf1", "hop:leaf2", "hop:spine"):
        assert hop in names
    for stage in ("route", "rank", "sort", "emit", "stats", "packetize",
                  "int_stamp"):
        assert stage in {s.name for s in tr.find(cat="stage")}, stage
    assert "pipeline" in names and "epoch:0" in names
    assert any(n.startswith("server0:") for n in names)
    assert any(n.startswith("merge:") or n.startswith("ladder:")
               for n in names)
    # hop spans carry in/out key counts for the per-hop bench breakdown
    spine = tr.find("hop:spine", cat="hop")[0]
    assert spine.args["keys"] == len(vals) == spine.args["keys_out"]
    assert res.telemetry is not None


def test_pipeline_telemetry_snapshot_counters_balance(vals):
    reg = MetricsRegistry()
    _run(vals, metrics=reg)
    snap = reg.snapshot()
    keys_in = snap["counters"]["hop_keys_in"]
    # the spine sees every key the leaves emitted
    assert keys_in["spine"] == len(vals) == sum(
        v for k, v in keys_in.items() if k.startswith("leaf")
    )
    assert "hop_emitted_run_length" in snap["histograms"]
    assert "server_max_reorder_depth" in snap["gauges"]


def test_pipeline_without_instrumentation_has_no_telemetry(vals):
    assert _run(vals).telemetry is None


@pytest.mark.parametrize("engine", ["fused", "device"])
def test_recording_tracer_builds_no_registry(vals, engine):
    """A tracer observes: telemetry comes from ``metrics=`` or INT only."""
    tr = Tracer()
    res = _run(vals, tracer=tr, engine=engine)
    assert res.telemetry is None
    assert "pipeline:ingress" in {s.name for s in tr.spans}


#: The spans of one ``engine="device"``, ``merge_backend="arena"`` job, and
#: the arg each carries.
JOB_SPANS = {
    "pipeline": "n",
    "pipeline:ingress": "keys",
    "epoch:build": "hops",
    "epoch:put": "bytes",
    "epoch:device": None,
    "epoch:fetch": "bytes",
    "server0:ingest": "keys",
    "server0:finish": None,
    "merge:seg0": "keys",
    "tournament:b": "bytes",
    "winners": "runs",
    "pipeline:result": None,
}


def test_spans_reach_the_profiler_trace(tmp_path, monkeypatch):
    """With no tracer passed, a profiled job's trace holds every span of
    the chip path, nested in the annotation around the job, with its args
    (as the benchmark's trace reader sees them)."""
    import importlib
    from pathlib import Path

    import jax

    from repro.net import device_epoch

    monkeypatch.syspath_prepend(Path(__file__).resolve().parents[1] / "bench")
    tracefile = importlib.import_module("tracefile")

    monkeypatch.setattr(device_epoch, "_PROGRAM_CACHE", {})
    keys = np.random.default_rng(3).integers(0, 1 << 15, size=40_000)
    kw = dict(
        num_segments=4, segment_length=16, max_value=1 << 15, num_flows=1,
        payload_size=64, engine="device", backend="numpy",
        merge_backend="arena",
    )
    jax.profiler.start_trace(str(tmp_path))
    try:
        with jax.profiler.TraceAnnotation(tracefile.JOB_SPAN):
            res = run_pipeline(keys, **kw)
    finally:
        jax.profiler.stop_trace()
    np.testing.assert_array_equal(res.output, np.sort(keys))
    trace = tracefile.load(tracefile.find_trace(tmp_path))
    (job,) = [ev for ev in trace.python if tracefile._is_job(ev)]
    found = {}
    for ev in trace.python:
        name = ev.args.get("long_name", ev.name)
        for want in JOB_SPANS:
            if name == want or (want.endswith(":b") and name.startswith(want)):
                assert job.start <= ev.start and ev.end <= job.end, name
                found.setdefault(want, ev)
    assert set(found) == set(JOB_SPANS)
    for want, arg in JOB_SPANS.items():
        if arg is not None:
            assert int(found[want].args[arg]) > 0, want
    assert int(found["pipeline"].args["n"]) == keys.size
    # The fetch carries the egress columns: vals, seq, sid, stream (int64)
    # and the run-break flags (bool), plus the per-hop scalars.
    assert int(found["epoch:fetch"].args["bytes"]) >= 33 * keys.size


def test_int_meta_depth_matches_fabric_depth(vals):
    single = _run(vals, topology="single", int_telemetry=True)
    assert single.delivered.int_meta.depth == 1
    leaf_spine = _run(vals, int_telemetry=True)
    assert leaf_spine.delivered.int_meta.depth == 2
    rows = leaf_spine.telemetry["int"]
    assert {r["depth"] for r in rows} == {0, 1}
    assert sum(r["keys"] for r in rows if r["depth"] == 0) == len(vals)


def test_int_meta_survives_jitter_and_server_pool(vals):
    res = _run(vals, int_telemetry=True, jitter_window=8,
               reorder_capacity=64, num_servers=4, range_mode="oracle")
    assert res.delivered.int_meta is not None
    assert len(res.delivered.int_meta) == len(res.delivered)
    np.testing.assert_array_equal(res.output, np.sort(vals))


@pytest.mark.parametrize("engine", ["segment", "faithful"])
def test_non_fused_engines_reject_int_telemetry(vals, engine):
    with pytest.raises(ValueError, match="does not support INT telemetry"):
        _run(vals[:500], int_telemetry=True, engine=engine)


def test_run_hop_int_stamp_is_byte_transparent(vals):
    from repro.net import interleave_batch, split_flows

    batch = interleave_batch(split_flows(vals, 4, 32), "round_robin")
    from repro.core.partition import set_ranges

    spec = HopSpec(SEGS, LENGTH, 1 << 16, set_ranges(1 << 16, SEGS),
                   payload_size=32)
    plain, _ = run_hop(batch, spec, "hop", "fused")
    stamped, _ = run_hop(batch, spec, "hop", "fused", int_telemetry=True,
                         hop_id=9)
    np.testing.assert_array_equal(plain.values, stamped.values)
    np.testing.assert_array_equal(plain.segment_id, stamped.segment_id)
    np.testing.assert_array_equal(plain.seq, stamped.seq)
    assert set(np.unique(stamped.int_meta.hop_id)) == {9}


# -- satellite: degenerate-pool accessors ------------------------------


def test_fresh_server_pool_accessors_do_not_raise():
    pool = ServerPool(SEGS, 4)
    assert pool.max_reorder_depth == 0
    assert pool.server_imbalance == 1.0
    assert pool.makespan_seconds == 0.0
    assert pool.server_keys == [0, 0, 0, 0]
    out, passes = pool.finish()  # draining an empty pool is legal
    assert out.size == 0 and len(passes) == SEGS
    assert pool.max_reorder_depth == 0 and pool.server_imbalance == 1.0
    assert pool.makespan_seconds >= 0.0
