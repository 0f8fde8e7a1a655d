"""Reduction of a JAX profiler trace (``.xplane.pb``) to device numbers.

A device plane is one named ``/device:TPU:<i>``; its operations are the
events of its ``XLA Ops`` line. Host spans are the events of every host
plane line whose name starts with one of the given prefixes (the harness's
``jax.profiler.TraceAnnotation`` spans, ``chipbench/...``). Timestamps are
nanoseconds on the trace's one clock.

* clock: the device planes' timestamps are the device's clock converted by
  the profiler, and lead or lag the host's by a millisecond or two. Every
  program a call runs lies inside that call's host span (a call waits for
  its result), so each device's events are shifted by the offset that puts
  the most of its ``XLA Modules`` executions inside the ``chipbench/call``
  spans: the middle of the best range of offsets, searched in 10 us steps
  within 20 ms either way.
* busy: the union of a device's operation intervals inside the window;
  idle share is 1 - busy / window.
* op time: the summed self time of the events of each operation name: an
  event's time minus that of the events nested in it (the line holds a
  while loop and, inside its interval, the operations of its body).
* exposed collective time: the part of the collective operations'
  intervals during which no other operation runs on that device.
* idle gaps: the intervals of the window in which a device runs nothing,
  named by the innermost host span open at the gap's midpoint.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re

__all__ = ["Event", "Trace", "read", "find_xplane", "clock_offset", "align",
           "union_ns", "summarize", "span_window"]

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
COLLECTIVE = re.compile(r"collective-permute|all-reduce|all-gather|reduce-scatter|all-to-all")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start: float      # ns
    end: float        # ns


@dataclasses.dataclass
class Trace:
    devices: dict      # device index -> sorted list[Event] of its operations
    host: list         # host spans, list[Event]
    modules: dict = dataclasses.field(default_factory=dict)  # device -> program runs


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "**", "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def read(path: str, host_prefixes=("chipbench/",)) -> Trace:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    devices, host, modules = {}, [], {}
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and line.name in (OPS_LINE, MODULES_LINE):
                into = devices if line.name == OPS_LINE else modules
                into.setdefault(int(m.group(1)), []).extend(
                    Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                    for e in line.events)
            elif not m and plane.name.startswith("/host"):
                host.extend(Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                            for e in line.events
                            if e.name.startswith(tuple(host_prefixes)))
    for evs in list(devices.values()) + list(modules.values()):
        evs.sort(key=lambda e: e.start)
    host.sort(key=lambda e: e.start)
    return Trace(devices=devices, host=host, modules=modules)


def clock_offset(modules: list, calls: list, reach: float = 20e6, step: float = 1e4) -> float:
    """Nanoseconds to add to a device's timestamps so that the most of its
    program runs ``modules`` lie inside a host ``calls`` span."""
    import numpy as np

    if not modules or not calls:
        return 0.0
    starts = np.array([c.start for c in calls])
    ends = np.array([c.end for c in calls])
    ms = np.array([e.start for e in modules])
    me = np.array([e.end for e in modules])
    deltas = np.arange(-reach, reach + step, step)
    inside = []
    for d in deltas:
        i = np.searchsorted(starts, ms + d, side="right") - 1
        ok = (i >= 0) & (me + d <= ends[np.clip(i, 0, None)])
        inside.append(int(ok.sum()))
    inside = np.array(inside)
    best = np.flatnonzero(inside == inside.max())
    run = best[: np.argmax(np.diff(np.append(best, best[-1] + 2)) > 1) + 1]
    return float(deltas[run].mean())


def align(trace: Trace, call_name: str = "chipbench/call") -> dict:
    """Shift each device's events onto the host clock; return the offsets."""
    calls = [h for h in trace.host if h.name == call_name]
    offsets = {}
    for dev in trace.devices:
        d = offsets[dev] = clock_offset(trace.modules.get(dev, []), calls)
        trace.devices[dev] = [dataclasses.replace(e, start=e.start + d, end=e.end + d)
                              for e in trace.devices[dev]]
        trace.modules[dev] = [dataclasses.replace(e, start=e.start + d, end=e.end + d)
                              for e in trace.modules.get(dev, [])]
    return offsets


def _merged(intervals, lo: float, hi: float) -> list:
    """Sorted, non-overlapping union of ``intervals`` clipped to [lo, hi]."""
    out = []
    for s, e in sorted((max(s, lo), min(e, hi)) for s, e in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def union_ns(intervals, lo: float = float("-inf"), hi: float = float("inf")) -> float:
    return sum(e - s for s, e in _merged(intervals, lo, hi))


def _exposed(events, lo, hi) -> float:
    """Time inside [lo, hi] covered by a collective and by no other op."""
    coll = [(e.start, e.end) for e in events if COLLECTIVE.search(e.name)]
    other = _merged([(e.start, e.end) for e in events if not COLLECTIVE.search(e.name)],
                    lo, hi)
    total = 0.0
    for s, e in _merged(coll, lo, hi):
        covered = sum(max(0.0, min(e, oe) - max(s, os_)) for os_, oe in other)
        total += (e - s) - covered
    return total


def _self_times(events, lo: float, hi: float) -> list:
    """(event, ns) pairs: each event's time inside [lo, hi] less the time of
    the events directly nested in it, never below 0."""
    out, stack = [], []
    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and e.start >= stack[-1][0].end:
            out.append(stack.pop())
        span = max(0.0, min(e.end, hi) - max(e.start, lo))
        if stack and e.end <= stack[-1][0].end:
            stack[-1][1] -= span
        stack.append([e, span])
    out.extend(stack)
    return [(e, max(0.0, ns)) for e, ns in out]


def _innermost(host, t: float) -> str:
    best = None
    for h in host:
        if h.start <= t <= h.end and (best is None or h.start >= best.start):
            best = h
    return best.name if best is not None else "(no host span)"


def summarize(trace: Trace, window: tuple, top: int = 10) -> dict:
    """Numbers of the traced window ``(start_ns, end_ns)``, per device and
    averaged over the devices."""
    lo, hi = window
    per_device, op_time, gaps = {}, {}, []
    for dev, events in sorted(trace.devices.items()):
        inside = [e for e in events if e.end > lo and e.start < hi]
        busy = _merged([(e.start, e.end) for e in inside], lo, hi)
        per_device[dev] = {
            "busy_s": sum(e - s for s, e in busy) * 1e-9,
            "exposed_collective_s": _exposed(inside, lo, hi) * 1e-9,
        }
        for e, ns in _self_times(inside, lo, hi):
            op_time[e.name] = op_time.get(e.name, 0.0) + ns * 1e-9
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for s, e in zip(edges[0::2], edges[1::2]):
            if e > s:
                gaps.append((e - s, (s + e) / 2))
    n = max(len(per_device), 1)
    named_gaps = {}
    for dur, mid in gaps:
        name = _innermost(trace.host, mid)
        named_gaps[name] = named_gaps.get(name, 0.0) + dur * 1e-9 / n
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(d["busy_s"] for d in per_device.values()) / n,
        "exposed_collective_s": sum(d["exposed_collective_s"] for d in per_device.values()) / n,
        "devices": per_device,
        "op_time_s": op_time,
        "device_ops": sorted(([k, v / n] for k, v in op_time.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in named_gaps.items()),
                            key=lambda kv: -kv[1])[:top],
    }


def span_window(trace: Trace, name: str) -> tuple:
    """(start, end) of the first host span called ``name``."""
    for h in trace.host:
        if h.name == name:
            return h.start, h.end
    raise KeyError(f"host span {name!r} not in the trace")
