"""The comparison that decides ``correct`` fails what it must.

The controls (``bench/control.py``) put the reference in the program's
place with one guarantee broken, and the fault cases drive a whole run of
the harness (set-up, window, judgement) with the chip checks skipped and
the timed path broken underneath.  Every one must come out not correct; the
sound run must come out correct.  Sizes are cut to what a CPU test holds.
"""

import dataclasses

import numpy as np
import pytest

import control
import reference
import run

SMALL = 1 << 14
SPEC = run._read_json(run.ROOT / "BENCHMARK.json")
CELLS = [w["name"] for w in SPEC["workloads"]]


def _cell(name: str) -> run.Cell:
    cell = run.load_cell(name)
    cell.config["keys_per_job"] = SMALL
    return cell


@pytest.mark.parametrize("name", CELLS)
@pytest.mark.parametrize("seed", [1, 2, 2**31 + 3])
def test_controls_fail_every_number_and_the_reference_passes(name, seed):
    cell = _cell(name)
    work = run.Workload(cell, seed)
    ref = work.reference()
    segments = int(work.kwargs["num_segments"])
    failed = set()
    for control_name, kw in control.CONTROLS.items():
        answer = reference.control_answer(
            work.keys, work.max_value, segments, **kw
        )
        nums = ref.compare(answer)
        over = {k for k, v in nums.items() if v > cell.limits[k]}
        assert over, (control_name, nums)
        failed |= over
    assert failed == set(cell.limits)
    exact = reference.control_answer(
        work.keys, work.max_value, segments, key_bits=None
    )
    assert all(v == 0 for v in ref.compare(exact).values())


def _unchanged(res, keys):
    """The step hands back its input as it came."""
    return dataclasses.replace(res, output=keys.copy())


def _half(res, keys):
    """Half of the job left out: only the first half of the keys sorted."""
    return dataclasses.replace(res, output=np.sort(keys[: keys.size // 2]))


def _key_altered(res, keys):
    out = res.output.copy()
    out[out.size // 2] += 1
    return dataclasses.replace(res, output=out)


def _misrouted(res, keys):
    sid = res.delivered.segment_id.copy()
    sid[0] = (sid[0] + 1) % (sid.max() + 1)
    wire = dataclasses.replace(res.delivered, segment_id=sid)
    return dataclasses.replace(res, delivered=wire)


def _wire_key_lost(res, keys):
    vals = res.delivered.values.copy()
    vals[-1] = vals[0]
    wire = dataclasses.replace(res.delivered, values=vals)
    return dataclasses.replace(res, delivered=wire)


FAULTS = [_unchanged, _half, _key_altered, _misrouted, _wire_key_lost]
CASES = [(c, f) for c in CELLS for f in [None, *FAULTS]]


@pytest.mark.parametrize(
    "name,fault", CASES,
    ids=[f"{c}-{f.__name__.strip('_') if f else 'sound'}" for c, f in CASES],
)
def test_a_broken_timed_path_is_not_correct(monkeypatch, name, fault):
    import repro.net

    real = repro.net.run_pipeline

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
