"""The readers of the wide-key path's per-layer metrics, on made-up traces
where every number is known.

The multi-word block sort returns its words as one stacked array, so its
HBM share counts every word through ``tracefile.op_bytes``.
"""

import gzip
import json

import pytest

import run
import tracefile

PEAKS = json.loads((run.BENCH / "peaks.json").read_text())["TPU v5 lite"]
READERS = [
    "wide_block_sort_hbm_share", "wide_merge_device_ms",
    "payload_gather_host_ms",
]
DEVICE, HOST = 3, 9
SORT_STACKED = (
    "%_sort_rows_padded_stacked = s32[4,512,128]{2,1,0:T(8,128)} "
    "custom-call(%b)"
)


def _meta(pid, tid, pname, tname):
    return [
        {"ph": "M", "pid": pid, "name": "process_name", "args": {"name": pname}},
        {"ph": "M", "pid": pid, "tid": tid, "name": "thread_name",
         "args": {"name": tname}},
    ]


def _x(pid, tid, name, ts, dur, **args):
    return {"ph": "X", "pid": pid, "tid": tid, "name": name, "ts": ts,
            "dur": dur, "args": args}


def _ctx(tmp_path, events):
    meta = (
        _meta(DEVICE, 2, "/device:TPU:0", "XLA Modules")
        + _meta(DEVICE, 3, "/device:TPU:0", "XLA Ops")
        + _meta(HOST, 1, "/host:CPU", "python")
    )
    path = tmp_path / "host.trace.json.gz"
    with gzip.open(path, "wt") as fh:
        json.dump({"traceEvents": meta + events}, fh)
    return tracefile.Context(tracefile.load(path), PEAKS)


def _job(tmp_path):
    """Two 1000 us jobs.  In each: a block sort whose four sort words come
    back as one stacked array (200 us), a stacked four-word merge program of 80 us
    beside a one-word merge program (the keys path's, not read here), and
    a payload gather of 120 us."""
    events = []
    for t0 in (0, 5000):
        events += [
            _x(HOST, 1, tracefile.JOB_SPAN, t0, 1000),
            _x(HOST, 1, "payload", t0 + 700, 120,
               long_name="pipeline:payload", bytes="9000"),
            _x(DEVICE, 3, SORT_STACKED, t0 + 100, 200,
               long_name=SORT_STACKED,
               tf_op="jit(epoch_fn)/hop_switch/block_sort/"
                     "jit(_sort_rows_padded_stacked)/pallas_call:"),
            _x(DEVICE, 2, "jit__merge_tournament_stacked(7)", t0 + 400, 80),
            _x(DEVICE, 2, "jit__merge_tournament(9)", t0 + 500, 30),
        ]
    return _ctx(tmp_path, events)


def test_every_stacked_word_is_counted():
    stacked = tracefile.Event("m", 0, 1, {"long_name": SORT_STACKED})
    assert tracefile.op_bytes(stacked) == 4 * 512 * 128 * 4


def test_wide_readers(tmp_path):
    ctx = _job(tmp_path)
    read = {name: run.load_reader(name)(ctx) for name in READERS}
    bw = PEAKS["hbm_bytes_per_s"]
    assert read["wide_block_sort_hbm_share"] == pytest.approx(
        100 * 2 * (4 * 512 * 128 * 4) / bw / 200e-6
    )
    assert read["wide_merge_device_ms"] == pytest.approx(0.08)
    assert read["payload_gather_host_ms"] == pytest.approx(0.12)


def test_nothing_to_read_gives_no_metric(tmp_path):
    """A trace of the one-word path (its kernels, its merge program, no
    payload span) gives none of the wide metrics."""
    one_word = "%_sort_rows_padded = s32[100,64]{1,0} custom-call()"
    ctx = _ctx(tmp_path, [
        _x(HOST, 1, tracefile.JOB_SPAN, 0, 1000),
        _x(DEVICE, 3, one_word, 10, 50, long_name=one_word,
           tf_op="jit(epoch_fn)/jit(_sort_rows_padded)/pallas_call:"),
        _x(DEVICE, 2, "jit__merge_tournament(9)", 100, 30),
    ])
    for name in READERS:
        assert run.load_reader(name)(ctx) is None, name
