"""The relation of bare int64 keys: the default of a configuration that
names no ``relation``.

The traffic file's value set is drawn by ``bench/traffic.py``, the job is
one ``repro.net.run_pipeline`` call on the keys, and the answer is held to
``bench/reference.py``'s two numbers, ``keys_wrong`` and
``delivery_wrong``.

A relation module provides:

* ``Workload(cell, seed)``: the relation drawn from the seed, outside the
  clock; ``.n`` (keys a job), ``.job(span)`` -> ``(seconds, answer)`` with
  the timed call inside ``span()``, and ``.reference()``, an object whose
  ``compare(answer)`` gives each number of ``correct``;
* ``exact(work)``: the reference in the program's place, nothing broken;
* ``CONTROLS``: answers with one guarantee broken, by name, built from the
  relation and the reference alone;
* ``FAULTS``: functions ``(res, keys) -> res`` that break the timed call's
  ``PipelineResult`` where it is produced.
"""

from __future__ import annotations

import contextlib
import dataclasses
import time

import numpy as np

import reference
import traffic
from faults import misrouted, unchanged


class Workload:
    """The cell's relation and the one call that sorts it."""

    def __init__(self, cell, seed: int):
        cfg = cell.config
        self.n = int(cfg["keys_per_job"])
        self.keys = traffic.draw_keys(cell.traffic, self.n, seed)
        self.max_value = traffic.max_value(cell.traffic)
        self.kwargs = dict(
            cfg["pipeline"], max_value=self.max_value, seed=seed % (1 << 32)
        )

    def job(self, span=contextlib.nullcontext):
        """Run one job inside ``span()``; return its seconds and answer."""
        import repro.net

        keys = self.keys.copy()
        with span():
            t0 = time.perf_counter()
            res = repro.net.run_pipeline(keys, **self.kwargs)
            seconds = time.perf_counter() - t0
        answer = reference.Answer(
            output=res.output,
            wire_keys=res.delivered.values,
            wire_segments=res.delivered.segment_id,
        )
        return seconds, answer

    def reference(self) -> reference.Reference:
        if self.kwargs.get("range_mode") != "static":
            raise SystemExit(
                "the delivery check knows Alg. 2's static ranges only"
            )
        return reference.Reference.build(
            self.keys, self.max_value, int(self.kwargs["num_segments"])
        )


def _answer(work: Workload, key_bits: int | None) -> reference.Answer:
    return reference.control_answer(
        work.keys, work.max_value, int(work.kwargs["num_segments"]),
        key_bits=key_bits,
    )


def exact(work: Workload) -> reference.Answer:
    return _answer(work, None)


#: ``int8_key``: keys held at 8 bits of precision, for the route and the
#: order alike, the step to a narrower key that would tempt a later change.
CONTROLS = {"int8_key": lambda work: _answer(work, 8)}


def _half(res, keys):
    """Half of the job left out: only the first half of the keys sorted."""
    return dataclasses.replace(res, output=np.sort(keys[: keys.size // 2]))


def _key_altered(res, keys):
    out = res.output.copy()
    out[out.size // 2] += 1
    return dataclasses.replace(res, output=out)


def _wire_key_lost(res, keys):
    vals = res.delivered.values.copy()
    vals[-1] = vals[0]
    wire = dataclasses.replace(res.delivered, values=vals)
    return dataclasses.replace(res, delivered=wire)


FAULTS = [unchanged, _half, _key_altered, misrouted, _wire_key_lost]
