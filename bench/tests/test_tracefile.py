"""The reduction from profiler trace to per-layer metrics.

One test builds a trace by hand, where every number is known; the others
read a small trace recorded on a TPU v5e (one job of 2^16 uniform keys
through a tree of seven switches to four merge servers, the python frames'
source paths stripped), checked in under ``bench/tests/data``.
"""

import gzip
import json
from pathlib import Path

import pytest

import run
import tracefile

DATA = Path(__file__).resolve().parent / "data"
PEAKS = json.loads((run.BENCH / "peaks.json").read_text())["TPU v5 lite"]
READERS = [
    "fabric_device_ms", "merge_device_ms", "block_sort_hbm_share",
    "tournament_hbm_share", "device_idle_share",
]


def _meta(pid, tid, pname, tname):
    return [
        {"ph": "M", "pid": pid, "name": "process_name", "args": {"name": pname}},
        {"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
         "args": {"name": tname}},
    ]


def _x(pid, tid, name, ts, dur, **args):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts,
            "dur": dur, "args": args}


def _write(tmp_path, events) -> Path:
    path = tmp_path / "plugins" / "profile" / "t" / "host.trace.json.gz"
    path.parent.mkdir(parents=True)
    with gzip.open(path, "wt") as fh:
        json.dump({"traceEvents": events}, fh)
    return path


def _made_up(tmp_path):
    """A 1000 us job: the epoch runs 100-500 us, a scatter for 150 us and a
    Pallas block sort of s32[1000,64] for 200 us inside it, a tournament of
    s32[16,128] runs 700-710 us, and the host is in ``pipeline.py``
    throughout."""
    sort_op = "jit(epoch_fn)/jit(_sort_rows_padded)/pallas_call:"
    events = (
        _meta(3, 2, "/device:TPU:0", "XLA Modules")
        + _meta(3, 3, "/device:TPU:0", "XLA Ops")
        + _meta(9, 1, "/host:CPU", "python")
        + [
            _x(9, 1, tracefile.JOB_SPAN, 0, 1000),
            _x(9, 1, "$pipeline.py:162 run_pipeline", 1, 998),
            _x(9, 1, "$fromnumeric.py:51 _wrapfunc", 600, 50),
            _x(3, 2, "jit_epoch_fn(123)", 100, 400),
            _x(3, 3, "%fusion.1 = u32[16] fusion()", 100, 150,
               tf_op="jit(epoch_fn)/scatter:"),
            _x(3, 3, "%_sort_rows_padded = s32[1000,64]{1,0} custom-call()",
               300, 200, tf_op=sort_op,
               long_name="%_sort_rows_padded = s32[1000,64]{1,0} custom-call()"),
            _x(3, 2, "jit__merge_tournament(9)", 700, 10),
            _x(3, 3, "%_merge_tournament = s32[16,128]{1,0} custom-call()",
               700, 10, tf_op="jit(_merge_tournament)/pallas_call:",
               long_name="%_merge_tournament = s32[16,128]{1,0} custom-call()"),
            _x(3, 3, "outside the job", 2000, 50),
        ]
    )
    log_dir = _write(tmp_path, events).parents[3]
    trace = tracefile.load(tracefile.find_trace(log_dir))
    return tracefile.Context(trace, PEAKS, frozenset({"pipeline.py"}))


def test_made_up_trace(tmp_path):
    ctx = _made_up(tmp_path)
    read = {name: run.load_reader(name)(ctx) for name in READERS}
    assert ctx.jobs == 1 and ctx.window_s == pytest.approx(1e-3)
    assert ctx.busy_s == pytest.approx(360e-6)
    assert read["fabric_device_ms"] == pytest.approx(0.4)
    assert read["merge_device_ms"] == pytest.approx(0.01)
    assert read["device_idle_share"] == pytest.approx(64.0)
    sort_bytes = 2 * 1000 * 64 * 4
    assert read["block_sort_hbm_share"] == pytest.approx(
        100 * sort_bytes / PEAKS["hbm_bytes_per_s"] / 200e-6
    )
    assert read["tournament_hbm_share"] == pytest.approx(
        100 * (2 * 16 * 128 * 4) / PEAKS["hbm_bytes_per_s"] / 10e-6
    )
    bd = ctx.breakdown()
    assert bd["device_ops"][0] == [
        "jit(epoch_fn)/jit(_sort_rows_padded)/pallas_call", pytest.approx(200e-6)
    ]
    assert bd["idle_gaps"][0] == [
        "pipeline.py:162 run_pipeline", pytest.approx(290e-6)
    ]
    assert [g[1] for g in bd["idle_gaps"]] == sorted(
        (g[1] for g in bd["idle_gaps"]), reverse=True
    )


def test_nothing_to_read_gives_no_metric(tmp_path):
    events = (
        _meta(3, 3, "/device:TPU:0", "XLA Ops")
        + _meta(9, 1, "/host:CPU", "python")
        + [_x(9, 1, tracefile.JOB_SPAN, 0, 100),
           _x(3, 3, "%sort.1 = s64[8] sort()", 10, 5, tf_op="jit(f)/sort:")]
    )
    path = _write(tmp_path, events)
    ctx = tracefile.Context(tracefile.load(path), PEAKS)
    for name in READERS[:4]:
        assert run.load_reader(name)(ctx) is None, name
    assert run.load_reader("device_idle_share")(ctx) == pytest.approx(95.0)


def test_a_trace_without_the_job_span_is_refused(tmp_path):
    path = _write(tmp_path, _meta(9, 1, "/host:CPU", "python"))
    with pytest.raises(ValueError):
        tracefile.Context(tracefile.load(path), PEAKS)


def test_union_length():
    assert tracefile.union_length([(0, 2), (1, 3), (5, 6), (5.5, 5.7)]) == 4
    assert tracefile.union_length([]) == 0


@pytest.fixture(scope="module")
def chip_ctx():
    trace = tracefile.load(DATA / "keys_random_65536.trace.json.gz")
    return tracefile.Context(trace, PEAKS, run._program_files())


def test_recorded_chip_trace_kernels(chip_ctx):
    sorts = chip_ctx.kernel_calls("jit(_sort_rows_padded)")
    merges = chip_ctx.kernel_calls("jit(_merge_tournament)")
    # 7 switch hops, each one block sort; the server merge's tournaments.
    assert len(sorts) == 7
    assert len(merges) > 0
    assert sorted({tracefile.op_bytes(ev) for ev in sorts}) == [
        272 * 64 * 4, 528 * 64 * 4, 1040 * 64 * 4,
    ]
    assert len(chip_ctx.modules("jit_epoch_fn")) == 1


def test_recorded_chip_trace_metrics(chip_ctx):
    read = {name: run.load_reader(name)(chip_ctx) for name in READERS}
    assert all(v is not None for v in read.values()), read
    assert 0 < read["device_idle_share"] < 100
    assert 0 < read["block_sort_hbm_share"] < 100
    assert 0 < read["tournament_hbm_share"] < 100
    assert 0 < read["merge_device_ms"] < read["fabric_device_ms"]
    assert chip_ctx.busy_s < chip_ctx.window_s
    bd = chip_ctx.breakdown()
    assert 0 < len(bd["device_ops"]) <= 10 and 0 < len(bd["idle_gaps"]) <= 10
    assert all(isinstance(name, str) and s > 0 for name, s in bd["idle_gaps"])
