"""The budget guard's halvings and syncs per true rollout step (``core/
infer.py`` ``guard_iters`` over the engine's ``rollout_steps``), over the
window."""
from bench.spans import delta


def read(rec):
    iters = delta(rec, "guard_iters")
    steps = delta(rec, "rollout_steps")
    if iters is None or not steps:
        return None
    return iters / steps
