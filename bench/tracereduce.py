"""Reduce a ``jax.profiler`` trace to the numbers the per-layer metrics read.

The trace is read into plain ``(start_ns, end_ns, name)`` tuples:

* device ops: the ``XLA Ops`` line of each ``/device:TPU:<n>`` plane;
* device programs: the ``XLA Modules`` line of the same planes, one event
  per execution of a jitted program, named by its jit name;
* host annotations: every host event whose name starts with ``bench.``
  (the ``jax.profiler.TraceAnnotation`` spans the harness opens).

The window is the ``bench.window`` annotation.  Busy time is the union of
a device's op intervals inside the window, averaged over the devices;
an idle gap is a stretch of the window in which no op ran, and is put to
the innermost annotation open at its middle (``-`` when none is).
"""

from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field

PREFIX = "bench."
WINDOW = "bench.window"


@dataclass
class Trace:
    window: tuple[int, int]
    #: device id -> sorted [(start, end, name)] of ops
    ops: dict[int, list] = field(default_factory=dict)
    #: device id -> sorted [(start, end, name)] of program executions
    modules: dict[int, list] = field(default_factory=dict)
    #: [(start, end, name)] host annotations, outermost first on ties
    annotations: list = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def find_xplane(trace_dir: str) -> str | None:
    found = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    )
    return found[-1] if found else None


def _device_id(plane_name: str) -> int | None:
    head, _, tail = plane_name.rpartition(":")
    if not head.startswith("/device:TPU") or not tail.isdigit():
        return None
    return int(tail)


def from_xplane(path: str) -> Trace | None:
    """Read an ``.xplane.pb``; ``None`` when it holds no ``bench.window``."""
    import jax

    data = jax.profiler.ProfileData.from_file(path)
    ops: dict[int, list] = {}
    modules: dict[int, list] = {}
    notes = []
    for plane in data.planes:
        dev = _device_id(plane.name)
        for line in plane.lines:
            if dev is not None and line.name in ("XLA Ops", "XLA Modules"):
                dest = ops if line.name == "XLA Ops" else modules
                dest.setdefault(dev, []).extend(
                    (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                    for e in line.events
                )
            elif dev is None and plane.name.startswith("/host:"):
                notes.extend(
                    (int(e.start_ns), int(e.start_ns + e.duration_ns), e.name)
                    for e in line.events
                    if e.name.startswith(PREFIX)
                )
    return build(ops, modules, notes)


def build(ops: dict, modules: dict, notes: list) -> Trace | None:
    wins = [n for n in notes if n[2] == WINDOW]
    if not wins:
        return None
    lo = min(w[0] for w in wins)
    hi = max(w[1] for w in wins)
    return Trace(
        window=(lo, hi),
        ops={d: sorted(v) for d, v in ops.items()},
        modules={d: sorted(v) for d, v in modules.items()},
        annotations=sorted(
            (n for n in notes if n[2] != WINDOW), key=lambda n: (n[0], -n[1])
        ),
    )


def union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged ``[(start, end)]`` of ``intervals`` clipped to ``[lo, hi]``."""
    out: list[list[int]] = []
    for s, e, *_ in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def gaps(busy: list[tuple[int, int]], lo: int, hi: int) -> list[tuple[int, int]]:
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def busy_s(trace: Trace) -> float | None:
    """Seconds some op ran, averaged over the traced devices."""
    if not trace.ops:
        return None
    lo, hi = trace.window
    per = [
        sum(e - s for s, e in union(v, lo, hi)) * 1e-9 for v in trace.ops.values()
    ]
    return sum(per) / len(per)


def annotation_at(trace: Trace, t: int) -> str:
    """Name of the innermost annotation open at ``t``."""
    best = None
    for s, e, name in trace.annotations:
        if s > t:
            break
        if e >= t and (best is None or s >= best[0]):
            best = (s, e, name)
    return best[2] if best else "-"


def idle_by_annotation(trace: Trace) -> dict[str, float]:
    """Idle seconds per innermost host annotation, averaged over devices."""
    lo, hi = trace.window
    out: dict[str, float] = {}
    n = max(len(trace.ops), 1)
    for v in trace.ops.values():
        for s, e in gaps(union(v, lo, hi), lo, hi):
            name = annotation_at(trace, (s + e) // 2)
            out[name] = out.get(name, 0.0) + (e - s) * 1e-9 / n
    return out


def seconds_by_name(events_by_device: dict, match=None) -> dict[str, float]:
    """Summed duration per event name over all devices, for the names
    where ``match(name)`` is true (all names without ``match``)."""
    out: dict[str, float] = {}
    for v in events_by_device.values():
        for s, e, name in v:
            if match is None or match(name):
                out[name] = out.get(name, 0.0) + (e - s) * 1e-9
    return out


def in_window(trace: Trace, events_by_device: dict) -> dict[int, list]:
    lo, hi = trace.window
    return {
        d: [ev for ev in v if ev[0] >= lo and ev[1] <= hi]
        for d, v in events_by_device.items()
    }


def outermost(events_by_device: dict) -> dict[int, list]:
    """Only the ops no other op encloses: the trace nests the ops inside
    a ``while`` or a fusion in the op that runs them."""
    out = {}
    for d, v in events_by_device.items():
        keep, end = [], None
        for ev in sorted(v, key=lambda e: (e[0], -e[1])):
            if end is None or ev[0] >= end:
                keep.append(ev)
                end = ev[1]
        out[d] = keep
    return out


def short_name(name: str) -> str:
    """``%while.48`` of ``%while.48 = (s32[], ...) while(...)``."""
    return name.split(" = ", 1)[0]


def top(totals: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(totals.items(), key=lambda kv: -kv[1])[:n]]
