"""The whole search step's share of the chip's bf16 peak: the operations
of the C x population x (generations + 1) evaluations of every whole call
in the window (``bench/work.py``), over the time of those calls, the chips
and the peak."""


def read(rec):
    if not rec.calls:
        return None
    return (100.0 * rec.eval_ops * rec.calls
            / (rec.window_s * rec.chips * rec.peaks["bf16_flops_per_s"]))
