"""Device self time of the decision transformer's decode (``core/
infer.py``, name scope ``dt_decode``) per fused rollout call, from the
trace."""
from bench.spans import events, scope_self_ms


def read(rec):
    ev = events(rec)
    return None if ev is None else scope_self_ms(ev, "jit__fused_batch",
                                                 "dt_decode")
