"""The reduction from a JAX profiler trace to the benchmark's numbers.

The profiler writes ``<dir>/plugins/profile/<time>/<host>.trace.json.gz``, a
Chrome trace: processes named ``/device:TPU:<i>`` hold a thread ``XLA Ops``
(one event per device operation, with ``args.tf_op``, the JAX name path such
as ``jit(epoch_fn)/jit(_sort_rows_padded)/pallas_call:``, and
``args.long_name``, the HLO instruction with its shape) and a thread ``XLA
Modules`` (one event per run of a compiled program, named
``jit_<function>(<fingerprint>)``).  On the host process ``/host:CPU``
the thread that ran the jobs (named after the interpreter) holds the
profiler's Python frames (``$<file>.py:<line> <function>``) and the
benchmark's own annotation, :data:`JOB_SPAN`, around each traced job.
Times are microseconds on one clock for host and device.

:class:`Context` is what a per-layer reader (``bench/metrics/<name>.py``)
gets: the device events inside the traced jobs and the chip's row of
``bench/peaks.json``.
"""

from __future__ import annotations

import dataclasses
import gzip
import json
import re
from pathlib import Path

#: The host annotation the harness puts around each traced job.
JOB_SPAN = "bench_job"

_ITEMSIZE = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "bf16": 2, "f16": 2,
    "s32": 4, "u32": 4, "f32": 4, "s64": 8, "u64": 8, "f64": 8,
}
_SHAPE = re.compile(r"= \(?(\w+)\[([\d,]*)\]")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float  # microseconds
    dur: float
    args: dict

    @property
    def end(self) -> float:
        return self.start + self.dur


@dataclasses.dataclass
class Trace:
    ops: dict[int, list[Event]]  # per device: its XLA Ops
    modules: dict[int, list[Event]]  # per device: its XLA Modules
    python: list[Event]  # the host thread that ran the jobs: its Python
    # frames and the benchmark's annotations


def find_trace(log_dir) -> Path:
    found = sorted(Path(log_dir).glob("plugins/profile/*/*.trace.json.gz"))
    if not found:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    return found[-1]


def load(path) -> Trace:
    with gzip.open(path, "rt") as fh:
        events = json.load(fh)["traceEvents"]
    procs, threads = {}, {}
    for e in events:
        if e.get("ph") != "M":
            continue
        if e.get("name") == "process_name":
            procs[e["pid"]] = e["args"]["name"]
        elif e.get("name") == "thread_name":
            threads[(e["pid"], e["tid"])] = e["args"]["name"]
    devices = {
        pid: int(name.rsplit(":", 1)[1])
        for pid, name in procs.items()
        if name.startswith("/device:TPU:")
    }
    trace = Trace(ops={}, modules={}, python=[])
    host: dict = {}
    for e in events:
        if e.get("ph") != "X":
            continue
        ev = Event(e["name"], float(e["ts"]), float(e["dur"]), e.get("args", {}))
        thread = threads.get((e["pid"], e.get("tid")))
        if e["pid"] in devices:
            dev = devices[e["pid"]]
            if thread == "XLA Ops":
                trace.ops.setdefault(dev, []).append(ev)
            elif thread == "XLA Modules":
                trace.modules.setdefault(dev, []).append(ev)
        elif procs.get(e["pid"]) == "/host:CPU":
            host.setdefault(e.get("tid"), []).append(ev)
    for lists in (trace.ops, trace.modules):
        for evs in lists.values():
            evs.sort(key=lambda ev: ev.start)
    for evs in host.values():
        if any(_is_job(ev) for ev in evs):
            trace.python = sorted(evs, key=lambda ev: (ev.start, -ev.dur))
            break
    return trace


def _is_job(ev: Event) -> bool:
    return JOB_SPAN in (ev.name, ev.args.get("long_name"))


def op_bytes(op: Event) -> int | None:
    """Bytes of the operation's (first) result, from its HLO shape."""
    m = _SHAPE.search(op.args.get("long_name", ""))
    if not m or m.group(1) not in _ITEMSIZE:
        return None
    size = _ITEMSIZE[m.group(1)]
    for dim in filter(None, m.group(2).split(",")):
        size *= int(dim)
    return size


def union_length(intervals) -> float:
    """Length covered by the union of ``(start, end)`` intervals."""
    total, reach = 0.0, float("-inf")
    for start, end in sorted(intervals):
        if end <= reach:
            continue
        total += end - max(start, reach)
        reach = end
    return total


def _clip(events, lo: float, hi: float):
    for ev in events:
        start, end = max(ev.start, lo), min(ev.end, hi)
        if end > start:
            yield start, end


class Context:
    """The traced window as the per-layer readers see it.

    ``jobs`` is the number of traced jobs; per-job numbers divide by it.
    ``program_files`` are the file names of the program's modules, whose
    Python frames label the idle gaps.
    """

    def __init__(self, trace: Trace, peaks: dict | None,
                 program_files: frozenset = frozenset()):
        spans = [ev for ev in trace.python if _is_job(ev)]
        if not spans:
            raise ValueError(f"the trace holds no {JOB_SPAN} span")
        self.trace = trace
        self.peaks = peaks
        self.jobs = len(spans)
        self.spans = [(ev.start, ev.end) for ev in spans]
        self.window_s = sum(end - start for start, end in self.spans) / 1e6
        self._program_files = program_files

    def _inside(self, events) -> list[Event]:
        return [
            ev for ev in events
            if any(lo <= ev.start and ev.end <= hi for lo, hi in self.spans)
        ]

    def ops(self) -> list[Event]:
        """Device operations of every chip, inside the traced jobs."""
        return [ev for evs in self.trace.ops.values() for ev in self._inside(evs)]

    def modules(self, prefix: str) -> list[Event]:
        """Runs of the compiled programs named ``<prefix>(...)``."""
        return [
            ev
            for evs in self.trace.modules.values()
            for ev in self._inside(evs)
            if ev.name.startswith(prefix + "(")
        ]

    def kernel_calls(self, tf_op: str) -> list[Event]:
        """Pallas kernel calls whose JAX name path contains ``tf_op``."""
        return [
            ev for ev in self.ops()
            if tf_op in ev.args.get("tf_op", "")
            and "pallas_call" in ev.args.get("tf_op", "")
        ]

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        per_chip = [
            union_length(
                iv for lo, hi in self.spans for iv in _clip(evs, lo, hi)
            )
            for evs in self.trace.ops.values()
        ]
        return sum(per_chip) / max(len(per_chip), 1) / 1e6

    def hbm_share(self, calls: list[Event]) -> float | None:
        """Least HBM time of ``calls`` (read and write their result once, at
        the chip's peak bandwidth) over their device time, in percent."""
        if not calls or not self.peaks:
            return None
        sizes = [op_bytes(ev) for ev in calls]
        if None in sizes:
            return None
        seconds = sum(ev.dur for ev in calls) / 1e6
        least = 2 * sum(sizes) / self.peaks["hbm_bytes_per_s"]
        return 100.0 * least / seconds

    def _host_label(self, t: float) -> str:
        """The innermost program frame on the host at time ``t``."""
        label = JOB_SPAN
        for ev in self.trace.python:
            if ev.start > t:
                break
            if ev.end >= t and ev.name.startswith("$"):
                file = ev.name[1:].split(":", 1)[0]
                if file in self._program_files:
                    label = ev.name[1:]
        return label

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time, by JAX name path,
        and the longest idle gaps, by what the host was doing."""
        by_name: dict[str, float] = {}
        for ev in self.ops():
            name = ev.args.get("tf_op", "").rstrip(":") or ev.args.get(
                "hlo_category", ev.name.split(" ")[0]
            )
            by_name[name] = by_name.get(name, 0.0) + ev.dur / 1e6
        device_ops = sorted(by_name.items(), key=lambda kv: -kv[1])[:top]
        gaps = []
        for evs in self.trace.ops.values():
            for lo, hi in self.spans:
                reach = lo
                for start, end in sorted(_clip(evs, lo, hi)):
                    if start > reach:
                        gaps.append((reach, start))
                    reach = max(reach, end)
                if hi > reach:
                    gaps.append((reach, hi))
        gaps.sort(key=lambda g: g[0] - g[1])
        idle = [
            [self._host_label((a + b) / 2), (b - a) / 1e6] for a, b in gaps[:top]
        ]
        return {"device_ops": [list(kv) for kv in device_ops], "idle_gaps": idle}
