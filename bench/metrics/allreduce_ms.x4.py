"""Device time per call of the collective ops inside the fused rollout
program (``jit__fused_batch``), averaged over the chips, from the trace.
With the tick's lanes sharded over the chips (``serving/replicas.py``),
the budget guard's ``while_loop`` under ``vmap`` continues while any lane
is over budget, so its predicate is all-reduced across the chips at every
guard iteration."""
from bench.trace import module_time


def read(rec):
    secs, calls = module_time(rec.trace, "jit__fused_batch")
    coll = rec.trace["collectives"].get("jit__fused_batch")
    return coll / calls * 1e3 if calls and coll else None
