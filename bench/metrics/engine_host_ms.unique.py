"""The engine's host time per device call (``serving/engine.py``): its
``engine.serve`` span less ``engine.wait``, over the window, from the
engine's span tallies in ``stats()``."""
from bench.spans import delta, span_seconds


def read(rec):
    serve = span_seconds(rec, (), "engine.serve")
    wait = span_seconds(rec, (), "engine.wait")
    calls = delta(rec, "device_calls")
    if serve is None or wait is None or not calls:
        return None
    return (serve - wait) / calls * 1e3
