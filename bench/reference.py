"""The plain reference and the comparison that decides ``correct``.

The reference is NumPy alone and imports nothing of the program: the sorted
keys are ``np.sort``, and each key's segment is its place in the paper's
Alg. 2 equal-width range table, written out again here.

Each job's result is held to two guarantees that the configuration
states, one number each, counted over all keys of the job:

* ``keys_wrong``: output positions whose key differs from the sorted
  relation, plus every key missing or extra;
* ``delivery_wrong``: keys on the egress wire whose segment id is not the
  segment whose range holds them, plus every position at which the wire's
  multiset of keys differs from the relation's (a key lost, duplicated or
  altered on the way).

Every limit is 0: the comparison is exact.
"""

from __future__ import annotations

import dataclasses

import numpy as np


def equal_width_bounds(max_value: int, num_segments: int) -> np.ndarray:
    """Exclusive upper bounds of Alg. 2's ranges over ``[0, max_value]``:
    ``q, r = divmod(max_value + 1, S)``, the first ``r`` ranges one wider."""
    q, r = divmod(int(max_value) + 1, int(num_segments))
    widths = np.full(num_segments, q, dtype=np.int64)
    widths[:r] += 1
    return np.cumsum(widths)


def positions_wrong(got: np.ndarray, want: np.ndarray) -> int:
    """Leading-axis positions at which ``got`` differs from ``want``, plus
    the difference in length."""
    got, want = np.asarray(got), np.asarray(want)
    m = min(len(got), len(want))
    diff = got[:m] != want[:m]
    if diff.ndim > 1:
        diff = diff.reshape(m, -1).any(axis=1)
    return int(np.count_nonzero(diff)) + abs(len(got) - len(want))


@dataclasses.dataclass
class Reference:
    """The relation's expected answers, computed once after the window."""

    sorted_keys: np.ndarray
    bounds: np.ndarray

    @classmethod
    def build(cls, keys, max_value: int, num_segments: int):
        return cls(
            sorted_keys=np.sort(keys),
            bounds=equal_width_bounds(max_value, num_segments),
        )

    def compare(self, answer) -> dict[str, int]:
        """The numbers of one job's ``answer``: ``output``, ``wire_keys``
        and ``wire_segments``."""
        keys = np.asarray(answer.wire_keys)
        sid = np.asarray(answer.wire_segments)
        home = np.searchsorted(self.bounds, keys, side="right")
        return {
            "keys_wrong": positions_wrong(answer.output, self.sorted_keys),
            "delivery_wrong": int(np.count_nonzero(home != sid))
            + positions_wrong(np.sort(keys), self.sorted_keys),
        }


@dataclasses.dataclass
class Answer:
    """What one job hands back, as the comparison reads it."""

    output: np.ndarray
    wire_keys: np.ndarray
    wire_segments: np.ndarray


def control_answer(keys, max_value: int, num_segments: int,
                   key_bits: int | None = 8) -> Answer:
    """A control: the reference in the program's place with one guarantee
    broken.  ``key_bits`` holds each key at that many bits of precision
    (the top bits of the key domain, rounded to nearest), as a narrower
    key type would, and both routes and sorts by it."""
    bounds = equal_width_bounds(max_value, num_segments)
    held = keys
    if key_bits is not None:
        shift = max(0, int(max_value).bit_length() - key_bits)
        if shift:
            held = ((keys + (1 << (shift - 1))) >> shift) << shift
        held = np.minimum(held, max_value)
    order = np.argsort(held, kind="stable")
    return Answer(
        output=keys[order],
        wire_keys=keys,
        wire_segments=np.searchsorted(bounds, held, side="right"),
    )
