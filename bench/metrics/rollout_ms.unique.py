"""Device time per call of the fused rollout program (``core/infer.py``
``_fused_batch``), averaged over the chips (on several, each holds its
share of the tick's lanes), from the trace."""
from bench.trace import module_time


def read(rec):
    secs, calls = module_time(rec.trace, "jit__fused_batch")
    return secs / calls * 1e3 if calls else None
