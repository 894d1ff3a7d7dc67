"""Share of the chip's memory bandwidth that decode needs at the rate it
ran in the traced window: the bytes each decode step must move (weights,
unembedding, the keys and values up to its position) times the steps
dispatched in the window, over the window and the peak bandwidth."""
from chipbench import flops as F


def read(view):
    c, tr = view.model, view.trace
    steps = tr.spans_in_window("chipbench.decode")
    if not steps or tr.window_s <= 0:
        return None
    total = sum(F.lm_decode_bytes(c, int(s.stats["batch"]),
                                  int(s.stats["pos"])) for s in steps)
    chips = max(len(tr.ops), 1)
    return 100.0 * total / tr.window_s / (chips * view.peaks["hbm_bytes_per_s"])
