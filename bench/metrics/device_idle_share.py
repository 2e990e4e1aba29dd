"""device_idle_share (%): the share of the traced window in which no
operation ran on the first device, 1 − union(op intervals) / window."""
from tracereduce import device_busy_ns


def read(m):
    w = m.window
    if w is None or not (w.ops or w.modules):
        return None
    dev = m.first_device(w)
    return 100.0 * (1.0 - device_busy_ns(w, dev) / (w.end_ns - w.start_ns))
