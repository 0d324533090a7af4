"""The engine's wait on the device per call (``serving/engine.py``
``engine.wait``: ``block_until_ready`` on the fused rollout's result),
over the window, from the engine's span tallies in ``stats()``."""
from bench.spans import delta, span_seconds


def read(rec):
    wait = span_seconds(rec, (), "engine.wait")
    calls = delta(rec, "device_calls")
    if wait is None or not calls:
        return None
    return wait / calls * 1e3
