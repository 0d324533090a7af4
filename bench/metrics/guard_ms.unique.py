"""Device self time of the budget guard's ``while_loop`` (``core/
infer.py``, name scope ``guard``) per fused rollout call, from the
trace."""
from bench.spans import events, scope_self_ms


def read(rec):
    ev = events(rec)
    return None if ev is None else scope_self_ms(ev, "jit__fused_batch",
                                                 "guard")
