"""idle_read_ms (ms per round): time in which no operation ran on the first
device while the host sat in a blocking read (the program's ``llcg.read``
spans)."""
from spans import idle_split_ns


def read(m):
    w = m.window
    if w is None or w.rounds == 0:
        return None
    split = idle_split_ns(w, m.first_device(w))
    return None if split is None else split[0] / w.rounds * 1e-6
