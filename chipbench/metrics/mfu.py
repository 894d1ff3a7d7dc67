"""Model FLOP/s utilisation of the traced window: the operations of the
prefills, decode steps and training steps the harness dispatched in it,
counted from their shapes (``chipbench.flops``), over the window and
the chip's peak."""
from chipbench import flops as F


def window_flops(view) -> float:
    c, tr = view.model, view.trace
    total = 0.0
    for s in tr.spans_in_window("chipbench.prefill"):
        total += F.lm_prefill_flops(c, int(s.stats["batch"]),
                                    int(s.stats["seq"]))
    for s in tr.spans_in_window("chipbench.decode"):
        total += F.lm_decode_flops(c, int(s.stats["batch"]),
                                   int(s.stats["pos"]))
    for s in tr.spans_in_window("chipbench.step"):
        total += F.encdec_train_flops(c, int(s.stats["batch"]),
                                      int(s.stats["seq"]),
                                      int(s.stats["enc_seq"]))
    return total


def read(view):
    total = window_flops(view)
    if total <= 0 or view.trace.window_s <= 0:
        return None
    chips = max(len(view.trace.ops), 1)
    return 100.0 * total / view.trace.window_s / (
        chips * view.peaks["bf16_flops_per_s"])
