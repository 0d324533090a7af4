"""The whole serving step's share of the chip's bf16 peak: the decision
transformer's matmul FLOPs of every request served on the device in the
window (``bench/work.py``: its real steps only, no padded lanes or masked
positions), over the window, the chips and the peak."""


def read(rec):
    if not rec.served_flops:
        return None
    return (100.0 * rec.served_flops
            / (rec.seconds * rec.chips * rec.peaks["bf16_flops_per_s"]))
