"""Reduction of a profiler trace to device busy time, kernel times and
idle gaps.

The JAX profiler writes ``<dir>/plugins/profile/<time>/<host>.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  Device planes are named
``/device:TPU:<n>``; their ``XLA Ops`` line holds one event per operation
run on that chip.  The window is the host-side ``bench.window``
annotation the harness opens and closes around the measured seconds, on
the same clock as the device events.

- busy: the union of a chip's op intervals inside the window;
- per-op time: the summed durations of each op name (its HLO text cut
  off) over all chips;
- idle gaps: the stretches of the window in which a chip ran nothing,
  each named by the host event that covers most of it (or "untraced host
  work" where no host event covers half of it, as in plain Python).

On a TPU v5e the device's events sit about a millisecond earlier than
the host's on this common clock (the recorded test trace shows 1.1 ms),
which is negligible against a window of seconds.
"""
from __future__ import annotations

import glob
import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Tuple

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"


class TraceError(Exception):
    """The profiler's trace cannot be read as the window's."""


@dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float
    text: str = ""       # the event's string stats, joined (shapes etc.)

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclass
class Trace:
    """Events by plane and line: ``planes[plane][line] -> [Event]``."""
    planes: Dict[str, Dict[str, List[Event]]] = field(default_factory=dict)

    def device_ops(self) -> Dict[str, List[Event]]:
        out = {}
        for plane, lines in sorted(self.planes.items()):
            if DEVICE_PLANE.match(plane):
                ops = lines.get(OPS_LINE)
                if ops is None:
                    ops = [e for evs in lines.values() for e in evs]
                out[plane] = sorted(ops, key=lambda e: e.start_ns)
        return out

    def host_events(self) -> List[Event]:
        return [e for plane, lines in self.planes.items()
                if not plane.startswith("/device")
                for evs in lines.values() for e in evs]


def read_xplane(path: str) -> Trace:
    from jax.profiler import ProfileData
    return from_planes(ProfileData.from_file(str(path)).planes)


def from_planes(planes) -> Trace:
    """A :class:`Trace` of the profiler's planes.  Lines of one plane that
    share a name (on a TPU's host, two threads both named ``python3``)
    are joined."""
    trace = Trace()
    for plane in planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        lines = trace.planes.setdefault(plane.name, {})
        for line in plane.lines:
            evs = lines.setdefault(line.name, [])
            for e in line.events:
                text = ""
                if device and line.name == OPS_LINE:
                    text = " ".join(str(v) for _, v in e.stats
                                    if isinstance(v, str))
                evs.append(Event(e.name, float(e.start_ns),
                                 float(e.duration_ns), text))
    return trace


def merge(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    out: List[List[float]] = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            out[-1][1] = max(out[-1][1], hi)
        else:
            out.append([lo, hi])
    return [(lo, hi) for lo, hi in out]


def clip(events: List[Event], lo: float, hi: float):
    return [(max(e.start_ns, lo), min(e.end_ns, hi)) for e in events
            if e.end_ns > lo and e.start_ns < hi]


def busy_ns(events: List[Event], lo: float, hi: float) -> float:
    return sum(b - a for a, b in merge(clip(events, lo, hi)))


def gaps(events: List[Event], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    out, t = [], lo
    for a, b in merge(clip(events, lo, hi)):
        if a > t:
            out.append((t, a))
        t = max(t, b)
    if hi > t:
        out.append((t, hi))
    return out


def window_of(trace: Trace, name: str) -> Optional[Tuple[float, float]]:
    for e in trace.host_events():
        if e.name == name:
            return e.start_ns, e.end_ns
    return None


@dataclass
class Reduced:
    """A traced window, reduced."""
    window_s: float
    busy_s_by_device: Dict[str, float]
    op_seconds: Dict[str, float]
    idle_gaps: List[Tuple[str, float]]
    kernel_events: Dict[str, List[Event]]

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the chips used."""
        vals = list(self.busy_s_by_device.values())
        return sum(vals) / len(vals) if vals else 0.0

    def events_named(self, fragment: str) -> List[Event]:
        return [e for evs in self.kernel_events.values() for e in evs
                if fragment in e.name or fragment in e.text]

    def breakdown(self) -> Dict:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, s] for n, s in ops],
                "idle_gaps": [[n, s] for n, s in self.idle_gaps[:10]]}


def _name_gap(host: List[Event], lo: float, hi: float, skip: str) -> str:
    """The host event that covers most of the gap, where it covers half of
    it or more; else the host ran something the trace does not record."""
    overlap: Dict[str, List[Tuple[float, float]]] = defaultdict(list)
    for e in host:
        if e.name == skip:
            continue
        a, b = max(e.start_ns, lo), min(e.end_ns, hi)
        if b > a:
            overlap[e.name].append((a, b))
    covered = {n: sum(b - a for a, b in merge(iv))
               for n, iv in overlap.items()}
    if not covered or 2 * max(covered.values()) < hi - lo:
        return "untraced host work"
    return max(covered.items(), key=lambda kv: kv[1])[0]


def op_name(hlo: str) -> str:
    """An op's name without its HLO text: the TPU's profiler names an
    ``XLA Ops`` event ``%fusion.3 = f32[...] fusion(...)``."""
    return hlo.split(" = ", 1)[0].lstrip("%")


def reduce(trace: Trace, window: str = "bench.window") -> Reduced:
    """Reduce the part of ``trace`` inside the host's ``window``
    annotation; a trace without it gives no reading."""
    ops = trace.device_ops()
    span = window_of(trace, window)
    if span is None:
        raise TraceError(f"no {window!r} annotation in the trace")
    lo, hi = span
    inside = {p: [e for e in evs if e.end_ns > lo and e.start_ns < hi]
              for p, evs in ops.items()}
    busy = {p: busy_ns(evs, lo, hi) / 1e9 for p, evs in inside.items()}
    op_s: Dict[str, float] = defaultdict(float)
    for evs in inside.values():
        for e in evs:
            op_s[op_name(e.name)] += (min(e.end_ns, hi)
                                      - max(e.start_ns, lo)) / 1e9
    named: List[Tuple[str, float]] = []
    if ops:
        host = trace.host_events()
        first = next(iter(ops.values()))
        longest = sorted(gaps(first, lo, hi), key=lambda g: g[0] - g[1])[:10]
        named = [(_name_gap(host, a, b, window), (b - a) / 1e9)
                 for a, b in longest]
    return Reduced(window_s=(hi - lo) / 1e9, busy_s_by_device=busy,
                   op_seconds=dict(op_s), idle_gaps=named,
                   kernel_events=inside)


def reduce_dir(directory: Path, window: str = "bench.window") -> Reduced:
    """Reduce the newest trace the profiler wrote under ``directory``."""
    found = sorted(glob.glob(str(Path(directory) / "**" / "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise TraceError(f"no profiler trace under {directory}")
    return reduce(read_xplane(found[-1]), window)
