"""Reduction of a profiler trace of the measured window to per-layer numbers.

The trace is first turned into plain ``Plane``/``Line``/``Event`` records
(:func:`planes_from_profile`), so the reduction below can be tested on a
synthesised trace.  Devices are the planes named ``/device:<KIND>:<id>``;
on each, the ``XLA Ops`` line holds the operations and the ``XLA Modules``
line the compiled programs, named ``jit_<function>`` plus a suffix.  The
window is marked on the host by the harness's own annotations: one
``bench.window_start`` and one ``bench.round_end`` per completed round.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW_START, ROUND_END = "bench.window_start", "bench.round_end"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
#: asynchronous operations (copies, collectives) run beside the ops above
ASYNC_OPS_LINE = "Async XLA Ops"
#: control-flow ops span the ops of their bodies; they are left out of the
#: top operations so that their time is not counted twice
_CONTAINER = re.compile(r"^(while|conditional|call)\b")
_DEVICE = re.compile(r"^/device:[A-Za-z]+:(\d+)")


@dataclasses.dataclass(frozen=True)
class Event:
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


@dataclasses.dataclass
class Line:
    name: str
    events: List[Event]


@dataclasses.dataclass
class Plane:
    name: str
    lines: List[Line]


@dataclasses.dataclass
class Window:
    """The traced window: its bounds, rounds, and each device's events."""

    start_ns: float
    end_ns: float
    rounds: int
    ops: Dict[int, List[Event]]
    modules: Dict[int, List[Event]]
    host: List[Event]
    async_ops: Dict[int, List[Event]] = dataclasses.field(
        default_factory=dict)

    @property
    def seconds(self) -> float:
        return (self.end_ns - self.start_ns) * 1e-9


def planes_from_profile(profile) -> List[Plane]:
    """Plain records of a ``jax.profiler.ProfileData``."""
    return [Plane(p.name, [Line(ln.name, [Event(e.name, float(e.start_ns),
                                                float(e.duration_ns))
                                          for e in ln.events])
                           for ln in p.lines])
            for p in profile.planes]


def load_trace(log_dir: str) -> List[Plane]:
    """The planes of the one ``*.xplane.pb`` a trace wrote under
    ``log_dir``."""
    from jax.profiler import ProfileData
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return planes_from_profile(ProfileData.from_file(found[-1]))


def _base(name: str) -> str:
    return name.split("#")[0]


def op_name(text: str) -> str:
    """An operation's instruction name: ``%fusion.7 = f32[...] fusion(...)``
    gives ``fusion.7``; a bare name stays as it is."""
    return text.split(" = ")[0].strip().lstrip("%")


def op_label(text: str) -> str:
    """A short label for the breakdown: the instruction, its shape and its
    opcode, without layouts and operands."""
    head = re.sub(r"\{[^}]*\}", "", text)
    name, eq, rest = head.partition(" = ")
    if not eq:
        return name.strip().lstrip("%")
    return f"{name.strip().lstrip('%')} = {rest.split('(')[0].strip()}"


def window_of(planes: Sequence[Plane]) -> Optional[Window]:
    """The window between the start marker and the last round marker, or
    None where the markers are missing."""
    host = [e for p in planes if p.name.startswith("/host:")
            for ln in p.lines for e in ln.events]
    starts = [e.start_ns for e in host if _base(e.name) == WINDOW_START]
    if not starts:
        return None
    lo = min(starts)
    ends = sorted(e.start_ns for e in host
                  if _base(e.name) == ROUND_END and e.start_ns > lo)
    if not ends:
        return None
    ops, modules, async_ops = {}, {}, {}
    for p in planes:
        m = _DEVICE.match(p.name)
        if not m:
            continue
        dev = int(m.group(1))
        for ln in p.lines:
            if ln.name == OPS_LINE:
                ops[dev] = list(ln.events)
            elif ln.name == MODULES_LINE:
                modules[dev] = list(ln.events)
            elif ln.name == ASYNC_OPS_LINE:
                async_ops[dev] = list(ln.events)
    return Window(lo, ends[-1], len(ends), ops, modules, host, async_ops)


def merged(events: Sequence[Event], lo: float, hi: float
           ) -> List[Tuple[float, float]]:
    """Union of the events' intervals, clipped to [lo, hi]."""
    spans = sorted((max(e.start_ns, lo), min(e.end_ns, hi)) for e in events
                   if e.end_ns > lo and e.start_ns < hi)
    out: List[Tuple[float, float]] = []
    for a, b in spans:
        if out and a <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], b))
        else:
            out.append((a, b))
    return out


def busy_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    return sum(b - a for a, b in merged(events, lo, hi))


def clipped_ns(events: Sequence[Event], lo: float, hi: float) -> float:
    return sum(max(0.0, min(e.end_ns, hi) - max(e.start_ns, lo))
               for e in events)


def module_ns(w: Window, device: int, prefix: str) -> Optional[float]:
    """Device time of the compiled programs named ``prefix``*, or None
    where the device ran none."""
    mods = [e for e in w.modules.get(device, [])
            if _base(e.name).startswith(prefix)]
    return clipped_ns(mods, w.start_ns, w.end_ns) if mods else None


def ops_ns(w: Window, device: int, pattern: str) -> Optional[float]:
    """Device time of the operations, synchronous or not, whose
    instruction name matches ``pattern``, or None where there are none."""
    rx = re.compile(pattern)
    hit = [e for e in w.ops.get(device, []) + w.async_ops.get(device, [])
           if rx.search(op_name(e.name))]
    return clipped_ns(hit, w.start_ns, w.end_ns) if hit else None


def device_busy_ns(w: Window, device: int) -> float:
    events = w.ops.get(device) or w.modules.get(device, [])
    return busy_ns(events, w.start_ns, w.end_ns)


def top_ops(w: Window, device: int, n: int = 10) -> List[List]:
    """The ``n`` operations that took most device time, control flow left
    out, by :func:`op_label`: [label, seconds]."""
    tot: Dict[str, float] = {}
    for e in w.ops.get(device, []):
        if _CONTAINER.match(op_name(e.name)):
            continue
        t = max(0.0, min(e.end_ns, w.end_ns) - max(e.start_ns, w.start_ns))
        if t > 0:
            label = op_label(e.name)
            tot[label] = tot.get(label, 0.0) + t
    ranked = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
    return [[k, v * 1e-9] for k, v in ranked]


def idle_gaps(w: Window, device: int, n: int = 10) -> List[List]:
    """The ``n`` longest idle gaps of the device in the window, each named
    by the innermost host event that spans the gap's midpoint:
    [name, seconds]."""
    events = w.ops.get(device) or w.modules.get(device, [])
    busy = merged(events, w.start_ns, w.end_ns)
    edges = [w.start_ns] + [x for ab in busy for x in ab] + [w.end_ns]
    gaps = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    gaps = sorted(gaps, key=lambda ab: ab[0] - ab[1])[:n]
    out = []
    for a, b in gaps:
        mid = 0.5 * (a + b)
        around = [e for e in w.host if e.start_ns <= mid <= e.end_ns
                  and e.dur_ns > 0]
        name = (_base(min(around, key=lambda e: e.dur_ns).name)
                if around else "(no host event)")
        out.append([name, (b - a) * 1e-9])
    return out
