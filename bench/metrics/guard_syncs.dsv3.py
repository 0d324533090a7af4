"""Steps whose proposed micro-batch the budget guard ended at SYNC, per
true rollout step (``core/infer.py`` ``guard_syncs`` over the engine's
``rollout_steps``), over the window.  A program without the counter
gives nothing."""
from bench.spans import delta


def read(rec):
    syncs = delta(rec, "guard_syncs")
    steps = delta(rec, "rollout_steps")
    if syncs is None or not steps:
        return None
    return syncs / steps
