"""driver_ms (ms per round): the plan driver's own host work, the self time
of the program's ``llcg.round`` spans less the ``llcg.read`` spans inside
them (sampling and correction-batch dispatch, program dispatch,
bookkeeping), mean over the rounds whose span lies whole in the window."""
from spans import driver_ns


def read(m):
    w = m.window
    if w is None:
        return None
    ns = driver_ns(w)
    return None if ns is None else ns * 1e-6
