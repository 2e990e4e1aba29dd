"""idle_host_ms (ms per round): time in which no operation ran on the first
device while the host was outside the program's ``llcg.read`` spans:
with ``idle_read_ms``, the whole idle time of ``device_idle_share``."""
from spans import idle_split_ns


def read(m):
    w = m.window
    if w is None or w.rounds == 0:
        return None
    split = idle_split_ns(w, m.first_device(w))
    return None if split is None else split[1] / w.rounds * 1e-6
