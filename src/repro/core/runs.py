"""Run detection and statistics (paper Def. 3.1.1 and §6.3).

A *Run* is a maximal ascending (non-decreasing) sub-sequence.  The paper
validates its analysis by collecting run counts and lengths of the switch
output; we expose the same statistics.
"""

from __future__ import annotations

import dataclasses

import numpy as np


def descents(a: np.ndarray) -> np.ndarray:
    """``a[1:] < a[:-1]``: where each next key falls below the one before
    it.  A 2-D ``a`` holds one key per row, as word tuples compared
    lexicographically (column 0 most significant)."""
    a = np.asarray(a)
    if a.ndim == 1:
        return a[1:] < a[:-1]
    nxt, prev = a[1:], a[:-1]
    below = nxt[:, -1] < prev[:, -1]
    for c in range(a.shape[1] - 2, -1, -1):
        below = (nxt[:, c] < prev[:, c]) | ((nxt[:, c] == prev[:, c]) & below)
    return below


def _falls_below(key, prev) -> bool:
    """Whether one key (a scalar, or a row of words) is below ``prev``."""
    if np.ndim(key) == 0:
        return int(key) < int(prev)
    return bool(descents(np.stack([prev, key]))[0])


def run_starts(a: np.ndarray) -> np.ndarray:
    """Indices where a new run starts (always includes 0 for non-empty a)."""
    a = np.asarray(a)
    if a.size == 0:
        return np.zeros(0, dtype=np.int64)
    breaks = np.nonzero(descents(a))[0] + 1
    return np.concatenate([[0], breaks]).astype(np.int64)


def run_lengths(a: np.ndarray) -> np.ndarray:
    starts = run_starts(a)
    if starts.size == 0:
        return starts
    return np.diff(np.concatenate([starts, [len(a)]]))


@dataclasses.dataclass(frozen=True)
class RunStats:
    n: int
    num_runs: int
    mean_len: float
    median_len: float
    min_len: int
    max_len: int

    @classmethod
    def of(cls, a: np.ndarray) -> "RunStats":
        lens = run_lengths(a)
        if lens.size == 0:
            return cls(0, 0, 0.0, 0.0, 0, 0)
        return cls(
            n=int(np.asarray(a).size),
            num_runs=int(lens.size),
            mean_len=float(lens.mean()),
            median_len=float(np.median(lens)),
            min_len=int(lens.min()),
            max_len=int(lens.max()),
        )


class RunArena:
    """Flat run storage for one segment: a contiguous keys buffer plus an
    offsets table, so closed runs are *slices*, not Python objects.

    The streaming server's arena merge backend appends each in-order payload
    columnarly (:meth:`feed` detects run breaks with one vectorized compare —
    no per-run Python), keeps the youngest run *open* so natural runs
    continue across packet boundaries exactly as Alg. 1 would see them, and
    at drain time hands the whole segment to the batched device merge as
    ``(keys, starts, lengths)`` — the layout
    :func:`repro.core.mergesort.merge_runs_flat` gathers into one padded
    tournament matrix without touching the runs individually.

    Buffers grow by doubling; both the keys buffer and the offsets table are
    int64 end to end (the index math must survive >2^31 keys — pinned by the
    regression tests in ``tests/test_run_arena.py``).  Wide keys (2-D
    payloads: one int32 row of sort words per key, the device epoch's words
    and input row) make the first feed turn the buffer into such rows; runs
    then break where the rows descend lexicographically.
    """

    def __init__(self, capacity: int = 1024) -> None:
        self._buf = np.empty(max(int(capacity), 1), dtype=np.int64)
        self._n = 0
        self._starts = np.zeros(16, dtype=np.int64)
        self._num_runs = 0

    def __len__(self) -> int:
        return self._n

    @property
    def num_runs(self) -> int:
        """Maximal ascending runs fed so far (the open run included)."""
        return self._num_runs

    @property
    def tail(self):
        """Last key of the open run (None while the arena is empty): an int,
        or a row of words."""
        if not self._n:
            return None
        last = self._buf[self._n - 1]
        return int(last) if last.ndim == 0 else last.copy()

    def _grow(self, arr: np.ndarray, need: int) -> np.ndarray:
        cap = len(arr)
        if need <= cap:
            return arr
        while cap < need:
            cap *= 2
        out = np.empty((cap,) + arr.shape[1:], dtype=arr.dtype)
        out[: len(arr)] = arr
        return out

    def _opens_new(self, arr: np.ndarray) -> bool:
        """Whether the payload ``arr`` opens a new run (its first key falls
        below the open run's last).  The first payload fixes the buffer's
        row shape."""
        if self._n == 0:
            if arr.ndim == 2:
                cap = max(len(self._buf), len(arr))
                self._buf = np.empty((cap, arr.shape[1]), np.int32)
            return True
        return _falls_below(arr[0], self._buf[self._n - 1])

    def feed(self, arr: np.ndarray) -> None:
        """Append one in-order payload; extend or break runs columnarly."""
        arr = np.asarray(arr)
        m = len(arr)
        if m == 0:
            return
        breaks = np.nonzero(descents(arr))[0] + 1
        opens_new = self._opens_new(arr)
        new_starts = breaks + self._n
        if opens_new:
            new_starts = np.concatenate([[self._n], new_starts])
        self._buf = self._grow(self._buf, self._n + m)
        self._buf[self._n : self._n + m] = arr
        self._n += m
        r = int(new_starts.size)
        if r:
            self._starts = self._grow(self._starts, self._num_runs + r)
            self._starts[self._num_runs : self._num_runs + r] = new_starts
            self._num_runs += r

    def feed_runs(self, arr: np.ndarray, starts: np.ndarray) -> None:
        """Append a payload whose run starts are already known.

        The compiled-epoch dataplane detects run breaks on device as part
        of the hop statistics, so its egress handoff carries ``starts``
        (the payload-relative break positions, ``starts[0] == 0`` for a
        non-empty payload) instead of making the arena re-scan the keys.
        Identical to :meth:`feed` of the same array — the open run still
        continues across the boundary when the first key does not descend.
        """
        arr = np.asarray(arr)
        m = len(arr)
        if m == 0:
            return
        starts = np.asarray(starts, dtype=np.int64)
        if starts.size == 0 or int(starts[0]) != 0:
            raise ValueError("run starts must begin at payload position 0")
        opens_new = self._opens_new(arr)
        new_starts = starts + self._n
        if not opens_new:
            new_starts = new_starts[1:]
        self._buf = self._grow(self._buf, self._n + m)
        self._buf[self._n : self._n + m] = arr
        self._n += m
        r = int(new_starts.size)
        if r:
            self._starts = self._grow(self._starts, self._num_runs + r)
            self._starts[self._num_runs : self._num_runs + r] = new_starts
            self._num_runs += r

    @property
    def keys(self) -> np.ndarray:
        """The contiguous key buffer (a view; runs are adjacent slices)."""
        return self._buf[: self._n]

    def run_offsets(self) -> tuple[np.ndarray, np.ndarray]:
        """``(starts, lengths)`` of every run, in arrival order."""
        starts = self._starts[: self._num_runs]
        lengths = np.diff(np.concatenate([starts, [self._n]]))
        return starts.copy(), lengths.astype(np.int64)


def merge_passes(num_runs: int, k: int) -> int:
    """Number of k-way merge iterations to reduce ``num_runs`` runs to one.

    The paper's ``log_k(ell)`` (§3.2); exact ceil-log for the discrete case.
    """
    if num_runs <= 1:
        return 0
    passes = 0
    while num_runs > 1:
        num_runs = -(-num_runs // k)
        passes += 1
    return passes
