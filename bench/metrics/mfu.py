"""mfu (%): the reading of round_mfu, for the cells its list leaves out:
model FLOPs of the traced window's rounds, from the configuration's counts
module, over the window's length times the chips times each chip's peak."""


def read(m):
    w = m.window
    if (w is None or not m.flops_per_round or not m.peak_flops
            or w.seconds <= 0):
        return None
    return (100.0 * m.flops_per_round * w.rounds
            / (w.seconds * m.chips * m.peak_flops))
