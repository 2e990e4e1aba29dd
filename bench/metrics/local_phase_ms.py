"""local_phase_ms (ms per round): device time on the first device of the
programs named ``jit_counted_round``*: the K local steps of every machine
and the averaging."""
from tracereduce import module_ns


def read(m):
    w = m.window
    if w is None or w.rounds == 0:
        return None
    ns = module_ns(w, m.first_device(w), "jit_counted_round")
    return None if ns is None else ns / w.rounds * 1e-6
