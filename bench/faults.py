"""Faults that any relation's ``PipelineResult`` can have, shared by the
relations' ``FAULTS`` (``bench/relations/``): functions ``(res, keys) ->
res`` that break the timed call's result where it is produced."""

from __future__ import annotations

import dataclasses


def unchanged(res, keys):
    """The step hands back its input as it came."""
    return dataclasses.replace(res, output=keys.copy())


def misrouted(res, keys):
    """One wire row carries the next segment's id."""
    sid = res.delivered.segment_id.copy()
    sid[0] = (sid[0] + 1) % (sid.max() + 1)
    wire = dataclasses.replace(res.delivered, segment_id=sid)
    return dataclasses.replace(res, delivered=wire)
