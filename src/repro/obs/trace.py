"""Hierarchical span tracer, on the profiler's clock, with Chrome export.

The dataplane's question is always *where did this job's time go* — which
layer, which stage of the epoch, which server, which merge.  Every span
opens a ``jax.profiler.TraceAnnotation`` under its name and args, so when
the JAX profiler runs, the program's spans and the device's operations
share one clock and one trace: an idle gap on the device falls inside a
named span of the host.  With no profiler running an annotation costs
about a microsecond.  ``jax`` is imported at the first span, not before.

A recording :class:`Tracer` also keeps its spans in memory: context
managers that record wall-clock intervals onto a flat event list, carrying
a category, a lane (Chrome ``tid`` — servers get their own lanes so the
pool's makespan reads off the timeline), and free-form args.
:meth:`Tracer.dump` writes the standard Chrome trace-event JSON
(``{"traceEvents": [...]}``), loadable in Perfetto / ``chrome://tracing``.
A tracer observes and never steers: the program runs the same code, and
the device the same compiled programs, with any tracer or none.

The default everywhere is :data:`NULL_TRACER`, a :class:`NullTracer` whose
``span()`` opens the annotation and records nothing.  Both tracers also
serve as the repo's **single wall-clock source**: :meth:`timed` always
measures (two ``perf_counter`` calls, even on the null tracer), exposes
``.seconds`` and opens no annotation, which is how the egress pool's
``per_server_seconds``/``makespan`` and the switchless baseline keep their
values with tracing off while sharing one timing code path with tracing on.
"""

from __future__ import annotations

import dataclasses
import json
import time


@dataclasses.dataclass
class Span:
    """One closed interval on the trace timeline."""

    name: str
    cat: str
    ts: float  # start, seconds since the tracer's origin
    dur: float  # duration, seconds
    tid: int  # lane (Chrome thread id); servers get distinct lanes
    depth: int  # nesting depth within its lane at open time
    args: dict

    @property
    def seconds(self) -> float:
        return self.dur


#: ``jax.profiler.TraceAnnotation``, imported at the first span.
_ANNOTATION = None


def _annotation(name: str, args: dict):
    """A profiler annotation of ``name`` and ``args``."""
    global _ANNOTATION
    if _ANNOTATION is None:
        from jax.profiler import TraceAnnotation

        _ANNOTATION = TraceAnnotation
    return _ANNOTATION(name, **args)


class _NullSpan:
    """The null tracer's span: a profiler annotation, recorded nowhere else."""

    __slots__ = ("_ann",)
    seconds = 0.0

    def __init__(self, name: str, args: dict) -> None:
        self._ann = _annotation(name, args)

    def __enter__(self) -> "_NullSpan":
        self._ann.__enter__()
        return self

    def __exit__(self, *exc) -> bool:
        self._ann.__exit__(*exc)
        return False

    def set(self, **args) -> None:
        """Attach args discovered mid-span to the annotation."""
        self._ann.set_metadata(**args)


class _Timed:
    """Measure-only interval: the null tracer's :meth:`~Tracer.timed`.

    Always runs the clock — results fields like ``per_server_seconds`` keep
    their values with tracing off — but records nothing.
    """

    __slots__ = ("_clock", "_t0", "seconds")

    def __init__(self, clock) -> None:
        self._clock = clock
        self.seconds = 0.0

    def __enter__(self) -> "_Timed":
        self._t0 = self._clock()
        return self

    def __exit__(self, *exc) -> bool:
        self.seconds = self._clock() - self._t0
        return False

    def set(self, **args) -> None:
        pass


class NullTracer:
    """The default tracer: records nothing, costs (almost) nothing.

    ``span()`` opens a profiler annotation and keeps no record;  ``timed()``
    still measures wall-clock (it is the repo's timing primitive) but keeps
    no record;  ``instant()`` is a no-op.  ``enabled`` lets hot paths skip
    building argument dicts that only a recording tracer keeps.
    """

    enabled = False
    clock = staticmethod(time.perf_counter)

    def span(self, name: str, cat: str = "", tid: int = 0, **args):
        return _NullSpan(name, args)

    def timed(self, name: str, cat: str = "", tid: int = 0, **args):
        return _Timed(self.clock)

    def instant(self, name: str, cat: str = "", tid: int = 0, **args) -> None:
        pass


#: Process-wide shared null tracer — the ``tracer or NULL_TRACER`` default.
NULL_TRACER = NullTracer()


class _RecordingSpan:
    """Context manager that appends a :class:`Span` to its tracer on exit,
    inside a profiler annotation when ``annotate`` (``span``, not
    ``timed``)."""

    __slots__ = ("_tracer", "_name", "_cat", "_tid", "_args", "_t0", "_depth",
                 "_ann", "seconds")

    def __init__(self, tracer: "Tracer", name: str, cat: str, tid: int,
                 args: dict, annotate: bool) -> None:
        self._tracer = tracer
        self._name = name
        self._cat = cat
        self._tid = tid
        self._args = args
        self._ann = _annotation(name, args) if annotate else None
        self.seconds = 0.0

    def __enter__(self) -> "_RecordingSpan":
        if self._ann is not None:
            self._ann.__enter__()
        self._depth = self._tracer._enter(self._tid)
        self._t0 = self._tracer.clock()
        return self

    def __exit__(self, *exc) -> bool:
        t1 = self._tracer.clock()
        if self._ann is not None:
            self._ann.__exit__(*exc)
        self.seconds = t1 - self._t0
        self._tracer._exit(self._tid)
        self._tracer.spans.append(
            Span(
                name=self._name,
                cat=self._cat,
                ts=self._t0 - self._tracer.origin,
                dur=self.seconds,
                tid=self._tid,
                depth=self._depth,
                args=self._args,
            )
        )
        return False

    def set(self, **args) -> None:
        """Attach args discovered mid-span (e.g. counts known after work)."""
        self._args.update(args)
        if self._ann is not None:
            self._ann.set_metadata(**args)


class Tracer:
    """Recording tracer: hierarchical spans + instant events, Chrome export.

    Spans nest per lane (``tid``): the dataplane runs on lane 0, egress
    servers on ``1 + server_index`` so the pool's simulated-parallel work
    renders as parallel tracks.  The span hierarchy the pipeline emits::

        pipeline
        ├─ pipeline:ingress          (flows split and interleaved)
        ├─ epoch:<e>
        │  ├─ hop:<name>             (cat="hop", per-hop engines)
        │  │  ├─ route / rank / sort / emit   (cat="stage")
        │  │  └─ stats / packetize           (cat="stage")
        │  └─ epoch:build / epoch:put / epoch:device / epoch:fetch
        │                            (cat="epoch", compiled device epoch)
        └─ pipeline:result           (per-segment streams, the result)
        server<s>:ingest             (cat="server", lane 1+s)
        └─ ladder:L<d>               (cat="server", eager k-way merges)
        server<s>:finish             (cat="server", lane 1+s)
        └─ merge:seg<sid>            (cat="server")
           └─ tournament:b<B> / winners      (cat="server", arena backend)
        pool:merge                   (cat="egress", distributed merge)

    All timestamps come from ``clock`` (default ``time.perf_counter``),
    relative to the tracer's construction time.
    """

    enabled = True

    def __init__(self, clock=time.perf_counter) -> None:
        self.clock = clock
        self.origin = clock()
        self.spans: list[Span] = []
        self.instants: list[Span] = []
        self._depths: dict[int, int] = {}

    # -- span bookkeeping ----------------------------------------------
    def _enter(self, tid: int) -> int:
        depth = self._depths.get(tid, 0)
        self._depths[tid] = depth + 1
        return depth

    def _exit(self, tid: int) -> None:
        self._depths[tid] = self._depths.get(tid, 1) - 1

    # -- public API -----------------------------------------------------
    def span(self, name: str, cat: str = "", tid: int = 0, **args):
        """Open a recorded span; use as a context manager."""
        return _RecordingSpan(self, name, cat, tid, args, True)

    def timed(self, name: str, cat: str = "", tid: int = 0, **args):
        """Like :meth:`span`, but measure-only on the profiler's side: the
        interval it times is a results field, and the spans inside it
        already annotate the work."""
        return _RecordingSpan(self, name, cat, tid, args, False)

    def instant(self, name: str, cat: str = "", tid: int = 0, **args) -> None:
        """A zero-duration event (control-plane handoffs, faults)."""
        self.instants.append(
            Span(
                name=name,
                cat=cat,
                ts=self.clock() - self.origin,
                dur=0.0,
                tid=tid,
                depth=self._depths.get(tid, 0),
                args=args,
            )
        )

    # -- queries --------------------------------------------------------
    def find(self, name: str | None = None, cat: str | None = None) -> list[Span]:
        """Spans matching a name and/or category (both exact)."""
        return [
            s
            for s in self.spans
            if (name is None or s.name == name)
            and (cat is None or s.cat == cat)
        ]

    def total_seconds(self, name: str | None = None, cat: str | None = None) -> float:
        """Summed duration of the matching spans."""
        return sum(s.dur for s in self.find(name, cat))

    # -- export ---------------------------------------------------------
    def chrome_trace(self) -> dict:
        """The trace as a Chrome trace-event JSON document (dict).

        Complete events (``"ph": "X"``) for spans, instant events
        (``"ph": "i"``) for the point events; timestamps in microseconds,
        as the format requires.  Viewable in Perfetto / ``chrome://tracing``.
        """
        events = [
            {
                "ph": "X",
                "name": s.name,
                "cat": s.cat or "default",
                "ts": s.ts * 1e6,
                "dur": s.dur * 1e6,
                "pid": 0,
                "tid": s.tid,
                "args": s.args,
            }
            for s in self.spans
        ] + [
            {
                "ph": "i",
                "name": s.name,
                "cat": s.cat or "default",
                "ts": s.ts * 1e6,
                "s": "t",  # thread-scoped instant
                "pid": 0,
                "tid": s.tid,
                "args": s.args,
            }
            for s in self.instants
        ]
        events.sort(key=lambda e: e["ts"])
        return {"traceEvents": events, "displayTimeUnit": "ms"}

    def dump(self, path: str) -> None:
        """Write :meth:`chrome_trace` as JSON to ``path``."""
        with open(path, "w") as fh:
            json.dump(self.chrome_trace(), fh, indent=1, default=_jsonable)
            fh.write("\n")


def _jsonable(obj):
    """Best-effort JSON fallback for numpy scalars/arrays in span args."""
    if hasattr(obj, "tolist"):
        return obj.tolist()
    if hasattr(obj, "item"):
        return obj.item()
    return str(obj)
