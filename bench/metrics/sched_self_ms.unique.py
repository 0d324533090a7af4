"""The scheduler's own host time per device call (``serving/
scheduler.py``): its ``scheduler.pump`` span less the engine's
``engine.serve`` inside it, over the window, from ``stats()``."""
from bench.spans import delta, span_seconds


def read(rec):
    pump = span_seconds(rec, ("scheduler",), "scheduler.pump")
    serve = span_seconds(rec, (), "engine.serve")
    calls = delta(rec, "device_calls")
    if pump is None or serve is None or not calls:
        return None
    return (pump - serve) / calls * 1e3
