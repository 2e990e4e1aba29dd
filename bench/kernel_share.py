"""A kernel's share of its roofline, for the per-layer readers of kernels.

The least time the chip could take for a kernel's work of one round is the
larger of its operations over the chip's peak FLOP/s and its bytes over the
chip's peak HBM bandwidth; the share is that time over the device time a
round of the operations named by the kernel's pattern took on the first
device.  The operations and bytes come from the configuration's counts
module (``Measured.work``), per round and chip; the time from the trace.
A reader of ``<kernel>_roofline`` is then::

    from kernel_share import share

    def read(m):
        return share(m, "<kernel>")
"""
from __future__ import annotations

from typing import Optional

from tracereduce import ops_ns


def share(m, label: str) -> Optional[float]:
    """100 × max(flops / peak FLOP/s, bytes / peak bytes/s) over the device
    seconds a round of the ops matching ``m.work[label]["pattern"]`` took
    on the first device; None without a trace, a matching op, the label's
    work or either peak."""
    w, work = m.window, m.work.get(label)
    if (w is None or w.rounds == 0 or work is None or not m.peak_flops
            or not m.peak_hbm_bytes_per_s):
        return None
    ns = ops_ns(w, m.first_device(w), work["pattern"])
    if not ns:
        return None
    least_s = max(work["flops"] / m.peak_flops,
                  work["bytes"] / m.peak_hbm_bytes_per_s)
    return 100.0 * least_s / (ns * 1e-9 / w.rounds)
