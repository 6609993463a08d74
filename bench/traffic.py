"""The benchmark's one traffic generator: a relation of integer keys drawn
from a traffic file's parameters.

A traffic file (``bench/traffic/<name>.json``) names a value set and how
often each value is drawn:

* ``values``: ``{"start", "step", "count"}``, the value set
  ``start + step * i`` for ``i < count``;
* ``weights``: absent for a uniform draw, else ``{"zipf": s, "shuffle":
  bool, "add": [[lo, hi, w], ...]}``: weight ``1 / (i + 1) ** s``,
  shuffled when asked, then ``w`` added to the slice ``lo:hi`` of the
  weights (``hi`` null for the end);
* ``repeat``: the chance that a key repeats the one before it, which makes
  the sequential-IO runs of a storage trace;
* ``loop`` and ``clients``: how jobs arrive; the harness runs a closed loop
  with one client and refuses anything else.

The three files under ``bench/traffic/`` reproduce the paper's evaluation
traces (arXiv:2103.14071, section 6), draw for draw the generators of
``repro.data.traces`` as they stood when the benchmark was written: uniform
over 32,768 uniques, packet lengths over 1,475 and IO sizes over 368.  The
copy lives here so that a change to the program cannot move the yardstick;
``bench/tests/test_traffic.py`` pins a digest of each one's output.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def rng_for(seed: int) -> np.random.Generator:
    """The generator of one ``--seed``, seeded with it as the paper's
    generators are."""
    return np.random.default_rng(int(seed) % (1 << 64))


def max_value(params: dict) -> int:
    v = params["values"]
    return int(v["start"] + v["step"] * (v["count"] - 1))


def draw_keys(params: dict, n: int, seed: int) -> np.ndarray:
    """``n`` int64 keys of the traffic ``params`` for ``seed``."""
    rng = rng_for(seed)
    v = params["values"]
    start, step, count = int(v["start"]), int(v["step"]), int(v["count"])
    w = params.get("weights")
    if w is None:
        keys = rng.integers(0, count, size=n, dtype=np.int64)
        keys = start + step * keys if (start, step) != (0, 1) else keys
    else:
        values = start + step * np.arange(count, dtype=np.int64)
        p = 1.0 / (np.arange(1, count + 1) ** float(w["zipf"]))
        if w.get("shuffle"):
            rng.shuffle(p)
        for lo, hi, add in w.get("add", ()):
            p[lo:hi] += add
        p /= p.sum()
        keys = rng.choice(values, size=n, p=p)
    rep = float(params.get("repeat", 0.0))
    if rep:
        again = rng.random(n) < rep
        again[:1] = False
        idx = np.arange(n)
        idx[again] = 0
        np.maximum.accumulate(idx, out=idx)
        keys = keys[idx]
    return keys

