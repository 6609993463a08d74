"""The relation of Sort Benchmark records: 100 bytes each, a 10-byte key
and a 90-byte payload (sortbenchmark.org, the GraySort/TeraSort record
format that ``gensort`` writes).

Draw (NumPy alone, from ``--seed``): ``n`` records.  Each key byte is
uniform, as in gensort's default mode (not ``-s``), so the keys are all
but unique.  Then ``round(tie_share * n)`` records, chosen from the seed,
take their first ``tie_bytes`` key bytes from as many other records,
keeping last bytes of their own that differ from the other record's: the
traffic's built prefix ties.  The payload follows gensort's framing with a
fixed filler, since gensort's own generator is not at hand: ``00 11``, the
record's row number as 32 upper-case ASCII hex digits, ``88 99 AA BB``,
the filler (``0123456789ABCDEF`` three times), ``CC DD EE FF``.

Why the ties: on uniform keys alone at 2^24 records, a sort on the first
eight key bytes is right with probability about 1 - 2^-17 (the chance that
no two keys share them), so the comparison could not tell a sort on a
64-bit prefix from one on the whole key.  With 8-byte ties, each tied pair
is ordered by its last two bytes, and the ``prefix_64`` control, which
routes and sorts on the first eight bytes alone, puts about half of the
pairs out of order.  gensort's skewed mode (``-s``) would give such ties
of its own, but its generator is not at hand.

Job: ``repro.net.run_pipeline(keys, payload=payload, **pipeline, seed=…)``
with ``keys`` an ``(n, 10)`` uint8 array, ordered as unsigned big-endian
bytes (memcmp order, the Sort Benchmark's own), and ``payload`` an
``(n, 90)`` uint8 array.  The answer is read from ``res.output`` (the
sorted keys, ``(n, 10)`` uint8), ``res.sorted_payload`` (``(n, 90)``
uint8), and the egress wire by row: ``res.delivered.row_index`` (the
input row of each wire row) and ``res.delivered.segment_id``.  How a wide
key rides the wire is the program's choice; the wire is read by row.
No program takes wide keys yet, so this call is the contract that the
program's wide-key change is held to.  Where that change needs another
call, its relation file takes ``draw``, ``Reference``, ``CONTROLS`` and
``FAULTS`` from this module (``from relations import gensort``) and
brings only its own ``Workload.job``: one draw, one reference.

Reference: a stable sort by key, ``np.lexsort`` on the key's last two
bytes under its first eight read as a big-endian uint64; nothing rides on
the payload.  A key's segment is its place in Alg. 2's equal-width table
over the whole key domain ``[0, 256**10)``, the bounds computed exactly as
Python ints and split into the same (first eight bytes, last two bytes)
pair, for any number of segments.  Two numbers, counted over all records
of a job:

* ``records_wrong``: output positions whose 100-byte record (key and
  payload) differs from the reference's, plus records missing or extra;
* ``delivery_wrong``: wire rows whose segment is not the home of their
  key, plus every row missing from the wire or there more than once.
"""

from __future__ import annotations

import contextlib
import dataclasses
import math
import time

import numpy as np

import traffic
from faults import misrouted, unchanged

#: The payload's fixed bytes around the row number's 32 hex digits.
_HEAD = bytes([0x00, 0x11])
_MID = bytes([0x88, 0x99, 0xAA, 0xBB])
_TAIL = bytes([0xCC, 0xDD, 0xEE, 0xFF])
_FILLER = b"0123456789ABCDEF" * 3
_ROW_DIGITS = 32
_HEX = np.frombuffer(b"0123456789ABCDEF", dtype=np.uint8)
#: Bytes of a key read as the leading uint64 of the (hi, lo) pair.
_HI_BYTES = 8


def _be_uint(cols: np.ndarray) -> np.ndarray:
    """Rows of at most eight bytes read as big-endian unsigned integers."""
    n, w = cols.shape
    out = np.zeros((n, 8), dtype=np.uint8)
    out[:, 8 - w:] = cols
    return out.view(">u8").ravel().astype(np.uint64)


def split_key(keys: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """``(n, key_bytes)`` uint8 keys as (first eight bytes, the rest), two
    uint64 columns whose lexicographic order is the keys' memcmp order."""
    return _be_uint(keys[:, :_HI_BYTES]), _be_uint(keys[:, _HI_BYTES:])


def payloads(rows: np.ndarray, payload_bytes: int) -> np.ndarray:
    """The payload of each record row, in the layout the module's
    docstring gives."""
    fixed = len(_HEAD) + _ROW_DIGITS + len(_MID) + len(_FILLER) + len(_TAIL)
    if payload_bytes != fixed:
        raise SystemExit(f"gensort payloads are {fixed} bytes, not "
                         f"{payload_bytes}")
    template = np.frombuffer(
        _HEAD + b"0" * _ROW_DIGITS + _MID + _FILLER + _TAIL, dtype=np.uint8
    )
    out = np.tile(template, (rows.size, 1))
    end = len(_HEAD) + _ROW_DIGITS
    digits = -(-int(rows.max(initial=0)).bit_length() // 4)
    rows = rows.astype(np.uint64)
    for d in range(digits):  # the leading digits stay "0"
        out[:, end - 1 - d] = _HEX[(rows >> np.uint64(4 * d)) & np.uint64(15)]
    return out


def draw(params: dict, n: int, seed: int) -> tuple[np.ndarray, np.ndarray]:
    """``n`` records of the traffic ``params`` for ``seed``: the keys,
    ``(n, key_bytes)`` uint8, and the payloads, ``(n, payload_bytes)``."""
    if params.get("key") != "uniform_bytes":
        raise SystemExit(f"gensort draws uniform key bytes only, not "
                         f"{params.get('key')!r}")
    key_bytes = int(params["key_bytes"])
    if not _HI_BYTES < key_bytes <= 2 * _HI_BYTES:
        raise SystemExit(f"keys of {key_bytes} bytes: the (hi, lo) pair "
                         "holds 9 to 16")
    tie_bytes, ties = int(params["tie_bytes"]), round(params["tie_share"] * n)
    if not 0 < tie_bytes < key_bytes or 2 * ties > n:
        raise SystemExit(f"{ties} ties of {tie_bytes} bytes do not fit "
                         f"{n} keys of {key_bytes}")
    rng = traffic.rng_for(seed)
    keys = rng.integers(0, 256, size=(n, key_bytes), dtype=np.uint8)
    rows = rng.choice(n, size=2 * ties, replace=False)
    tied, donor = rows[:ties], rows[ties:]
    keys[tied, :tie_bytes] = keys[donor, :tie_bytes]
    same = (keys[tied] == keys[donor]).all(axis=1)
    keys[tied[same], -1] ^= 1  # a tie, never a duplicate
    return keys, payloads(np.arange(n), int(params["payload_bytes"]))


def segment_bounds(key_bytes: int, num_segments: int) -> list[int]:
    """Exclusive upper bounds of Alg. 2's equal-width ranges over
    ``[0, 256**key_bytes)``, as Python ints: ``q, r = divmod(256**key_bytes,
    S)``, the first ``r`` ranges one wider."""
    q, r = divmod(256 ** key_bytes, num_segments)
    bounds, b = [], 0
    for s in range(num_segments):
        b += q + (s < r)
        bounds.append(b)
    return bounds


def home_segments(keys: np.ndarray, num_segments: int) -> np.ndarray:
    """Each key's segment in the equal-width table: how many of the first
    ``S - 1`` bounds are at or below it, compared as (hi, lo) pairs."""
    key_bytes = keys.shape[1]
    lo_bits = 8 * (key_bytes - _HI_BYTES)
    inner = segment_bounds(key_bytes, num_segments)[:-1]
    bhi = np.array([b >> lo_bits for b in inner], dtype=np.uint64)
    blo = np.array([b & ((1 << lo_bits) - 1) for b in inner], dtype=np.uint64)
    khi, klo = split_key(keys)
    below = np.searchsorted(bhi, khi, side="left")  # hi below: bound <= key
    level = np.searchsorted(bhi, khi, side="right")
    home = below.astype(np.int64)
    for t in range(int((level - below).max(initial=0))):
        j = below + t  # a bound with the key's hi: compare the lo words
        tie = j < level
        home += tie & (blo[np.minimum(j, len(inner) - 1)] <= klo)
    return home


def sort_order(keys: np.ndarray) -> np.ndarray:
    """The stable permutation that sorts the keys in memcmp order."""
    hi, lo = split_key(keys)
    return np.lexsort((lo, hi))


def _rows_wrong(got_keys, got_payload, want_keys, want_payload) -> int:
    """Positions whose record differs, plus records missing or extra."""
    got_keys, got_payload = np.asarray(got_keys), np.asarray(got_payload)
    m = min(len(got_keys), len(got_payload), len(want_keys))
    bad = np.count_nonzero(
        (_as_void(got_keys[:m]) != _as_void(want_keys[:m]))
        | (_as_void(got_payload[:m]) != _as_void(want_payload[:m]))
    )
    return int(bad) + len(want_keys) - m + max(
        len(got_keys), len(got_payload)) - m


def _as_void(a: np.ndarray) -> np.ndarray:
    """Rows of bytes as one opaque value each, compared whole."""
    a = np.ascontiguousarray(a, dtype=np.uint8)
    return a.view(f"V{a.shape[1]}").ravel()


@dataclasses.dataclass
class Answer:
    """What one job hands back, as the comparison reads it."""

    keys: np.ndarray
    payload: np.ndarray
    wire_rows: np.ndarray
    wire_segments: np.ndarray


@dataclasses.dataclass
class Reference:
    """The relation's expected answers: the sorted records and each input
    row's home segment."""

    keys: np.ndarray
    payload: np.ndarray
    home: np.ndarray

    @classmethod
    def build(cls, keys, payload, num_segments: int):
        order = sort_order(keys)
        return cls(keys=keys[order], payload=payload[order],
                   home=home_segments(keys, num_segments))

    def compare(self, answer: Answer) -> dict[str, int]:
        n = self.home.size
        rows = np.asarray(answer.wire_rows, dtype=np.int64)
        sid = np.asarray(answer.wire_segments)
        valid = (rows >= 0) & (rows < n)
        seen = np.bincount(rows[valid], minlength=n)
        return {
            "records_wrong": _rows_wrong(
                answer.keys, answer.payload, self.keys, self.payload
            ),
            "delivery_wrong": int(np.count_nonzero(~valid))
            + int(np.count_nonzero(self.home[rows[valid]] != sid[valid]))
            + int(np.abs(seen - 1).sum()),
        }


class Workload:
    """The cell's records and the one call that sorts them."""

    def __init__(self, cell, seed: int):
        cfg = cell.config
        self.n = int(cfg["keys_per_job"])
        self.keys, self.payload = draw(cell.traffic, self.n, seed)
        self.kwargs = dict(cfg["pipeline"], seed=seed % (1 << 32))
        self.num_segments = int(self.kwargs["num_segments"])

    def job(self, span=contextlib.nullcontext):
        """Run one job inside ``span()``; return its seconds and answer."""
        import repro.net

        keys, payload = self.keys.copy(), self.payload.copy()
        with span():
            t0 = time.perf_counter()
            res = repro.net.run_pipeline(keys, payload=payload, **self.kwargs)
            seconds = time.perf_counter() - t0
        answer = Answer(
            keys=res.output,
            payload=res.sorted_payload,
            wire_rows=res.delivered.row_index,
            wire_segments=res.delivered.segment_id,
        )
        return seconds, answer

    def reference(self) -> Reference:
        if self.kwargs.get("range_mode") != "static":
            raise SystemExit(
                "the delivery check knows Alg. 2's static ranges only"
            )
        return Reference.build(self.keys, self.payload, self.num_segments)


def _held(work: Workload, order, route_keys) -> Answer:
    """The records in ``order``, each wire row routed by ``route_keys``."""
    return Answer(
        keys=work.keys[order],
        payload=work.payload[order],
        wire_rows=order,
        wire_segments=home_segments(route_keys, work.num_segments)[order],
    )


def exact(work: Workload) -> Answer:
    return _held(work, sort_order(work.keys), work.keys)


def _payload_unsorted(work: Workload) -> Answer:
    """The keys sorted, the payload left in input order."""
    answer = exact(work)
    return dataclasses.replace(answer, payload=work.payload.copy())


def _prefix_bits(work: Workload) -> Answer:
    """Keys held to fewer leading bits than ``log2(num_segments)`` (the
    rest zero), for the route and the order alike."""
    bits = max(0, math.ceil(math.log2(work.num_segments)) - 1)
    held = work.keys.copy()
    whole, part = divmod(bits, 8)
    held[:, whole] &= (0xFF << (8 - part)) & 0xFF
    held[:, whole + 1:] = 0
    return _held(work, sort_order(held), held)


def _prefix_64(work: Workload) -> Answer:
    """Keys held to their first eight bytes, the width of one uint64, for
    the route and the order alike: the step to a narrower key that wide
    keys tempt."""
    held = work.keys.copy()
    held[:, _HI_BYTES:] = 0
    return _held(work, sort_order(held), held)


def _wire_row_lost(work: Workload) -> Answer:
    """One wire row lost, another there twice."""
    answer = exact(work)
    rows = answer.wire_rows.copy()
    sid = answer.wire_segments.copy()
    rows[-1], sid[-1] = rows[0], sid[0]
    return dataclasses.replace(answer, wire_rows=rows, wire_segments=sid)


CONTROLS = {
    "payload_unsorted": _payload_unsorted,
    "prefix_bits": _prefix_bits,
    "prefix_64": _prefix_64,
    "wire_row_lost": _wire_row_lost,
}


def _half(res, keys):
    """Half of the job left out: the first half of the sorted records."""
    h = len(res.output) // 2
    return dataclasses.replace(
        res, output=res.output[:h], sorted_payload=res.sorted_payload[:h]
    )


def _key_altered(res, keys):
    out = res.output.copy()
    out[len(out) // 2, -1] ^= 1
    return dataclasses.replace(res, output=out)


def _payload_altered(res, keys):
    pay = res.sorted_payload.copy()
    pay[len(pay) // 2, -5] ^= 1
    return dataclasses.replace(res, sorted_payload=pay)


def _wire_row_dup(res, keys):
    rows = res.delivered.row_index.copy()
    rows[-1] = rows[0]
    wire = dataclasses.replace(res.delivered, row_index=rows)
    return dataclasses.replace(res, delivered=wire)


FAULTS = [unchanged, _half, _key_altered, _payload_altered, misrouted,
          _wire_row_dup]
