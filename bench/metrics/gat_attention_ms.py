"""gat_attention_ms (ms per round): device time on the first device of the
operations that match the ``gat_attention`` kernel's pattern from the
configuration's counts module (``Measured.work``): the Pallas kernel that
gathers and sums GAT's neighbour rows in the forward passes."""
from tracereduce import ops_ns


def read(m):
    w, work = m.window, m.work.get("gat_attention")
    if w is None or w.rounds == 0 or work is None:
        return None
    ns = ops_ns(w, m.first_device(w), work["pattern"])
    return None if ns is None else ns / w.rounds * 1e-6
