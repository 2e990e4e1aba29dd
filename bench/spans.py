"""The program's own host spans in the traced window.

The program marks each round with an ``llcg.round`` span and each blocking
read of a device result with an ``llcg.read`` span
(``repro.core.engine.span``), on the clock of the device planes.  So the
device's idle time splits into the part in which the host waited on a read
and the part in which it did its own work, and a round's host time splits
into reads and the rest.  Spans are clipped to the window.  The trace stops
inside the last round of the window (the harness's hook ends it there), so
that round's ``llcg.round`` span is not recorded: per-round host times are
means over the rounds whose span lies whole in the window.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from tracereduce import Event, Window, _base, merged

ROUND, READ = "llcg.round", "llcg.read"


def named(w: Window, name: str) -> List[Event]:
    return [e for e in w.host if _base(e.name) == name]


def idle_split_ns(w: Window, device: int) -> Optional[Tuple[float, float]]:
    """The device's idle time in the window inside ``llcg.read`` spans and
    outside them, or None where the window holds no read or no device
    event."""
    reads = merged(named(w, READ), w.start_ns, w.end_ns)
    events = w.ops.get(device) or w.modules.get(device, [])
    if not reads or not events:
        return None
    busy = merged(events, w.start_ns, w.end_ns)
    edges = [w.start_ns] + [x for ab in busy for x in ab] + [w.end_ns]
    idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    in_reads = _overlap_ns(idle, reads)
    return in_reads, sum(b - a for a, b in idle) - in_reads


def _overlap_ns(xs: List[Tuple[float, float]],
                ys: List[Tuple[float, float]]) -> float:
    """Length of the intersection of two sorted lists of disjoint
    intervals."""
    total, i, j = 0.0, 0, 0
    while i < len(xs) and j < len(ys):
        lo, hi = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        total += max(0.0, hi - lo)
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return total


def driver_ns(w: Window) -> Optional[float]:
    """Mean self time of the ``llcg.round`` spans whole in the window, less
    the ``llcg.read`` spans inside each, or None where there are none."""
    rounds = [e for e in named(w, ROUND)
              if e.start_ns >= w.start_ns and e.end_ns <= w.end_ns]
    if not rounds:
        return None
    reads = named(w, READ)
    return sum(r.dur_ns - sum(b - a for a, b in
                              merged(reads, r.start_ns, r.end_ns))
               for r in rounds) / len(rounds)
