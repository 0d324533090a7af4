"""Device time per call of the G-Sampler grid program (``core/gsampler.py``
``_ga_grid``), from the trace."""
from bench.trace import module_time


def read(rec):
    secs, calls = module_time(rec.trace, "jit__ga_grid")
    return secs / calls * 1e3 if calls else None
