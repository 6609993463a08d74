"""The comparison that decides ``correct`` fails what it must.

The controls (each relation's ``CONTROLS``, ``bench/relations/``) put the
reference in the program's place with one guarantee broken, and the fault
cases drive a whole run of the harness (set-up, window, judgement) with
the chip checks skipped and the timed path broken underneath (the
relation's ``FAULTS``).  Every one must come out not correct; the sound run
must come out correct.  Sizes are cut to what a CPU test holds.

Besides the cells of ``BENCHMARK.json``, two cells that exist only here
run Sort Benchmark records (``relation: gensort``) through the unchanged
harness; the program cannot sort keys that wide yet, so a NumPy stand-in of
the records contract takes its place.
"""

import dataclasses

import numpy as np
import pytest

import run
import traffic

SMALL = 1 << 14
SPEC = run._read_json(run.ROOT / "BENCHMARK.json")
#: Test-only records cells: records a job and segments.
GENSORT = {"gensort_s16": (1 << 14, 16), "gensort_s10": (1 << 12, 10)}
CELLS = [w["name"] for w in SPEC["workloads"]] + list(GENSORT)


def _cell(name: str) -> run.Cell:
    if name in GENSORT:
        n, segments = GENSORT[name]
        return run.Cell(
            name=name,
            chips=1,
            config={
                "relation": "gensort",
                "keys_per_job": n,
                "pipeline": {"num_segments": segments, "range_mode": "static"},
            },
            traffic=traffic.load("gensort_uniform"),
            limits={"records_wrong": 0, "delivery_wrong": 0},
            end_to_end=SPEC["end_to_end"],
            per_layer=[],
        )
    cell = run.load_cell(name)
    cell.config["keys_per_job"] = SMALL
    return cell


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_controls_fail_every_number_and_the_reference_passes(name, seed):
    cell = _cell(name)
    relation = run.load_relation(cell)
    work = relation.Workload(cell, seed)
    ref = work.reference()
    failed = set()
    for control_name, make in relation.CONTROLS.items():
        nums = ref.compare(make(work))
        over = {k for k, v in nums.items() if v > cell.limits[k]}
        assert over, (control_name, nums)
        failed |= over
    assert failed == set(cell.limits)
    assert all(v == 0 for v in ref.compare(relation.exact(work)).values())


@dataclasses.dataclass
class _Wire:
    row_index: np.ndarray
    segment_id: np.ndarray


@dataclasses.dataclass
class _Records:
    output: np.ndarray
    sorted_payload: np.ndarray
    delivered: _Wire


def _records_stand_in(keys, *, payload, num_segments, width=None, **_kw):
    """A program of the records contract: a stable sort of the keys as byte
    strings, and each row's segment from its key as a Python int by Alg.
    2's closed form.  Sound where ``width`` is None; otherwise it sorts and
    routes on the first ``width`` key bytes alone, the rest read as 0."""
    width = keys.shape[1] if width is None else width
    order = np.array(
        sorted(range(len(keys)), key=lambda i: keys[i, :width].tobytes()),
        dtype=np.int64,
    )
    q, r = divmod(256 ** keys.shape[1], num_segments)
    edge = r * (q + 1)

    def home(key) -> int:
        v = int.from_bytes(key[:width].tobytes(), "big")
        v <<= 8 * (keys.shape[1] - width)
        return v // (q + 1) if v < edge else r + (v - edge) // q

    wire = _Wire(
        row_index=order,
        segment_id=np.array([home(k) for k in keys[order]], dtype=np.int64),
    )
    return _Records(
        output=keys[order], sorted_payload=payload[order], delivered=wire
    )


CASES = [
    (c, f) for c in CELLS for f in [None, *run.load_relation(_cell(c)).FAULTS]
]


@pytest.mark.parametrize(
    "name,fault", CASES,
    ids=[f"{c}-{f.__name__.strip('_') if f else 'sound'}" for c, f in CASES],
)
def test_a_broken_timed_path_is_not_correct(monkeypatch, name, fault):
    import repro.net

    real = _records_stand_in if name in GENSORT else repro.net.run_pipeline

    def broken(keys, **kw):
        res = real(keys, **kw)
        return fault(res, keys) if fault else res

    monkeypatch.setattr(repro.net, "run_pipeline", broken)
    result = run.run_cell(_cell(name), 2**31 + 11, 0.2, False, check_chip=False)
    assert result["attempted"] >= 1
    assert result["correct"] is (fault is None), result["checks"]
    if fault is not None:
        assert result["failed"] == result["attempted"]
    assert list(result)[-1] == "checks"


@pytest.mark.parametrize("name", list(GENSORT))
def test_a_sort_on_a_64_bit_prefix_is_not_correct(monkeypatch, name):
    """The records traffic's prefix ties hold the whole key: a program that
    sorts and routes on the first eight key bytes fails every job."""
    import repro.net

    def prefix_sort(keys, **kw):
        return _records_stand_in(keys, width=8, **kw)

    monkeypatch.setattr(repro.net, "run_pipeline", prefix_sort)
    result = run.run_cell(_cell(name), 2**31 + 13, 0.2, False, check_chip=False)
    assert result["attempted"] >= 1
    assert result["correct"] is False
    assert result["failed"] == result["attempted"]
    assert result["checks"]["records_wrong"]["value"] > 0
