"""Mean wait of a queued request before its flush starts (``serving/
scheduler.py``: ``wait_s`` over ``requests`` on the ``scheduler.flush``
spans of the traced stretch).  The scheduler's ``queue_wait_s`` counter
is not used: over the whole window of a traced run it also holds the
requests that waited while the profiler stopped."""
from bench.spans import events, host_spans


def read(rec):
    ev = events(rec)
    if ev is None:
        return None
    meta = [m for _, _, _, m in host_spans(ev, "scheduler.flush")
            if "wait_s" in m]
    n = sum(m["requests"] for m in meta)
    return sum(m["wait_s"] for m in meta) / n * 1e3 if n else None
