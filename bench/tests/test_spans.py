"""The readers of the program's own spans (``bench/spans.py``).

The made-up traces put every number where it is known; the recorded one
is a TPU v5e trace of one job of 2^16 uniform keys through the
``keys_random`` cell's fabric (one switch, one merge server), checked in
under ``bench/tests/data``.
"""

import gzip
import json
from pathlib import Path

import pytest

import run
import spans
import tracefile

DATA = Path(__file__).resolve().parent / "data"
PEAKS = json.loads((run.BENCH / "peaks.json").read_text())["TPU v5 lite"]
READERS = [
    "ingress_host_ms", "result_host_ms", "epoch_fetch_ms",
    "epoch_fetch_bytes_per_key", "merge_host_ms",
]
HOST, DEVICE = 9, 3


def _x(pid, name, ts, dur, **args):
    return {"ph": "X", "pid": pid, "tid": 1, "name": name, "ts": ts,
            "dur": dur, "args": args}


def _span(full, ts, dur, **args):
    """A program span as the profiler writes it: ``layer:part`` becomes
    ``part`` with the full name in ``long_name``; arg values are strings."""
    args = {k: str(v) for k, v in args.items()}
    if ":" in full:
        args["long_name"] = full
    return _x(HOST, full.rsplit(":", 1)[-1], ts, dur, **args)


def _ctx(tmp_path, events):
    meta = [
        {"ph": "M", "pid": DEVICE, "name": "process_name",
         "args": {"name": "/device:TPU:0"}},
        {"ph": "M", "pid": DEVICE, "tid": 1, "name": "thread_name",
         "args": {"name": "XLA Ops"}},
        {"ph": "M", "pid": HOST, "name": "process_name",
         "args": {"name": "/host:CPU"}},
        {"ph": "M", "pid": HOST, "tid": 1, "name": "thread_name",
         "args": {"name": "python"}},
    ]
    path = tmp_path / "host.trace.json.gz"
    with gzip.open(path, "wt") as fh:
        json.dump({"traceEvents": meta + events}, fh)
    return tracefile.Context(tracefile.load(path), PEAKS)


def _job_events():
    """One 1000 us job of 1000 keys.  Ingress 10-110 us holds a program
    span (20 us), a Python frame and a runtime event; the epoch puts,
    runs on the device 160-480 us and fetches 33,000 bytes in 100 us; the
    server ingests 610-650 us and finishes 650-900 us, where a tournament
    runs 40 us on the device; the result takes 80 us."""
    return [
        _x(HOST, tracefile.JOB_SPAN, 0, 1000),
        _x(HOST, "$pipeline.py:1 run_pipeline", 1, 998),
        _span("pipeline", 2, 988, n=1000),
        _span("pipeline:ingress", 10, 100, keys=1000),
        _x(HOST, "$flow.py:1 interleave_batch", 20, 80),
        _x(HOST, "PjitFunction(f)", 30, 10),
        _span("control:split_epochs", 50, 20),
        _span("epoch:0", 120, 480, keys=1000),
        _span("epoch:put", 120, 30, bytes=8000),
        _span("epoch:device", 150, 350),
        _x(DEVICE, "%scatter.1 = s64[1000] scatter()", 160, 320),
        _span("epoch:fetch", 500, 100, bytes=33000),
        _span("server0:ingest", 610, 40, keys=1000),
        _span("server0:finish", 650, 250),
        _span("merge:seg0", 660, 230, keys=1000),
        _span("tournament:b128", 700, 100, runs=4, bytes=4096),
        _x(DEVICE, "%merge = s32[4,128] custom-call()", 720, 40),
        _span("winners", 820, 60, runs=2),
        _span("pipeline:result", 900, 80),
        # After the job: not counted.
        _span("epoch:fetch", 2000, 100, bytes=99),
    ]


def _read(ctx):
    return {name: run.load_reader(name)(ctx) for name in READERS}


def test_made_up_job(tmp_path):
    read = _read(_ctx(tmp_path, _job_events()))
    assert read["ingress_host_ms"] == pytest.approx(0.08)
    assert read["result_host_ms"] == pytest.approx(0.08)
    assert read["epoch_fetch_ms"] == pytest.approx(0.1)
    assert read["epoch_fetch_bytes_per_key"] == pytest.approx(33.0)
    # 40 + 250 us in the server spans, less the tournament's 40 on device.
    assert read["merge_host_ms"] == pytest.approx(0.25)


def test_per_job_over_two_jobs(tmp_path):
    second = []
    for ev in _job_events()[:-1]:
        ev = dict(ev, ts=ev["ts"] + 1000)
        if ev["name"] == "fetch":
            ev["dur"] = 300
        second.append(ev)
    read = _read(_ctx(tmp_path, _job_events()[:-1] + second))
    assert read["epoch_fetch_ms"] == pytest.approx(0.2)
    assert read["ingress_host_ms"] == pytest.approx(0.08)
    assert read["epoch_fetch_bytes_per_key"] == pytest.approx(33.0)


def test_names_split_at_the_colon_are_read_from_long_name(tmp_path):
    ctx = _ctx(tmp_path, _job_events())
    names = [spans.full_name(ev) for ev in spans.program_spans(ctx)]
    assert "epoch:fetch" in names and "fetch" not in names
    assert "pipeline" in names and "winners" in names
    assert not any(n.startswith(("$", "PjitFunction")) for n in names)
    # A bare "fetch" (no long_name) is some other event, not the span.
    events = [ev for ev in _job_events() if ev["name"] != "fetch"]
    events.append(_x(HOST, "fetch", 500, 100, bytes="33000"))
    read = _read(_ctx(tmp_path, events))
    assert read["epoch_fetch_ms"] is None
    assert read["epoch_fetch_bytes_per_key"] is None


def test_self_time_and_idle_arithmetic(tmp_path):
    ctx = _ctx(tmp_path, _job_events())
    progs = spans.program_spans(ctx)
    (finish,) = spans.named(ctx, "server0:finish")
    (seg,) = spans.named(ctx, "merge:seg0")
    assert spans.self_us(finish, progs) == pytest.approx(250 - 230)
    assert spans.self_us(seg, progs) == pytest.approx(230 - 100 - 60)
    assert spans.idle_us(ctx, [(600, 700), (650, 760)]) == pytest.approx(
        160 - 40
    )
    assert spans.idle_us(ctx, []) == 0


def test_no_spans_no_metrics(tmp_path):
    """A program that opens no spans (the benchmark's job annotation,
    Python frames and runtime events only) gives no span metric."""
    events = [
        ev for ev in _job_events()
        if ev["name"].startswith("$") or ev["name"] in (
            tracefile.JOB_SPAN, "PjitFunction(f)"
        ) or ev["pid"] == DEVICE
    ]
    read = _read(_ctx(tmp_path, events))
    assert read == dict.fromkeys(READERS)


@pytest.fixture(scope="module")
def chip_ctx():
    trace = tracefile.load(DATA / "keys_random_65536_spans.trace.json.gz")
    return tracefile.Context(trace, PEAKS, run._program_files())


def test_recorded_chip_trace_spans(chip_ctx):
    read = _read(chip_ctx)
    assert all(v is not None and v > 0 for v in read.values()), read
    # vals, seq, sid, stream (int64) and the run breaks (bool) a key, plus
    # the per-hop counts and run totals.
    assert 33.0 < read["epoch_fetch_bytes_per_key"] < 33.1
    (job,) = chip_ctx.spans
    assert sum(read[m] for m in READERS[:3]) < (job[1] - job[0]) / 1e3
    names = {spans.full_name(ev) for ev in spans.program_spans(chip_ctx)}
    assert {
        "pipeline", "pipeline:ingress", "epoch:put", "epoch:device",
        "epoch:fetch", "server0:ingest", "server0:finish", "pipeline:result",
    } <= names
    old = [
        "fabric_device_ms", "merge_device_ms", "block_sort_hbm_share",
        "tournament_hbm_share", "device_idle_share",
    ]
    assert all(run.load_reader(m)(chip_ctx) is not None for m in old)
    stages = {
        op.args.get("tf_op", "").split("/")[2]
        for op in chip_ctx.ops()
        if op.args.get("tf_op", "").startswith("jit(epoch_fn)/hop_switch/")
    }
    assert stages >= {"route", "rank", "block_sort", "emit", "ship"}
