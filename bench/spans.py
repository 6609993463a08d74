"""The program's own spans in a profiler trace, as the span readers see them.

Every span the program opens through ``repro.obs.trace`` is also a
profiler annotation on the host thread that ran the jobs, on the clock of
the device's operations.  The profiler writes an annotation named
``layer:part`` as ``name: "part"`` with ``args.long_name: "layer:part"``,
and every arg value as a string; a name without ``:`` stays as it is.  A
span's full name is therefore ``args.long_name``, else ``name``.

The same thread holds the profiler's Python frames (``$<file>.py:<line>``)
and the runtime's own events (``PjitFunction(...)``, ``DevicePut``, ...).
Neither is a program span: a program span is one whose full name is in the
program's vocabulary, :data:`PROGRAM_SPAN`.

A span's self time is its duration minus the union of the program spans
nested in it.  Readers count only spans inside the traced jobs
(:data:`tracefile.JOB_SPAN`), and return ``None`` where the trace has none
of the spans they read: a program that opens no such span.
"""

from __future__ import annotations

import re

import tracefile

#: The full names of the spans ``repro.obs.trace`` opens in the program.
PROGRAM_SPAN = re.compile(
    r"(pipeline|winners|route|rank|sort|emit|stats|packetize|int_stamp)"
    r"|(pipeline|epoch|control|hop|server\d+|ladder|merge|tournament"
    r"|egress|mt|pool):.+"
)


def full_name(ev: tracefile.Event) -> str:
    return ev.args.get("long_name", ev.name)


def program_spans(ctx: tracefile.Context) -> list[tracefile.Event]:
    """The program's spans inside the traced jobs, in start order."""
    return [
        ev for ev in ctx._inside(ctx.trace.python)
        if not ev.name.startswith("$")
        and PROGRAM_SPAN.fullmatch(full_name(ev))
    ]


def named(ctx: tracefile.Context, pattern: str,
          among: list[tracefile.Event] | None = None) -> list[tracefile.Event]:
    """The program's spans (or those ``among``) whose full name matches
    ``pattern`` whole."""
    if among is None:
        among = program_spans(ctx)
    return [ev for ev in among if re.fullmatch(pattern, full_name(ev))]


def arg(ev: tracefile.Event, key: str) -> float:
    return float(ev.args[key])


def self_us(ev: tracefile.Event, spans: list[tracefile.Event]) -> float:
    """``ev``'s duration less the union of the ``spans`` nested in it."""
    inner = [
        (s.start, s.end) for s in spans
        if s is not ev and ev.start <= s.start and s.end <= ev.end
    ]
    return ev.dur - tracefile.union_length(inner)


def self_ms_per_job(ctx: tracefile.Context, pattern: str) -> float | None:
    """Summed self time of the spans named ``pattern``, per job, in ms."""
    spans = program_spans(ctx)
    hits = named(ctx, pattern, spans)
    if not hits:
        return None
    return sum(self_us(ev, spans) for ev in hits) / 1e3 / ctx.jobs


def idle_us(ctx: tracefile.Context, intervals) -> float:
    """Time inside the union of ``intervals`` in which no operation runs on
    any chip."""
    merged: list[list[float]] = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], end)
        else:
            merged.append([start, end])
    ops = [ev for evs in ctx.trace.ops.values() for ev in evs]
    return sum(
        (hi - lo) - tracefile.union_length(tracefile._clip(ops, lo, hi))
        for lo, hi in merged
    )
