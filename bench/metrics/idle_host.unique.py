"""Share of the traced stretch in which the device is idle while the
innermost open host span is one of the scheduler's or the engine's other
than ``engine.wait``: the serving host path holding the chip back."""
from bench.spans import events, host_spans, idle_by_span


def read(rec):
    ev = events(rec)
    if ev is None or not host_spans(ev, "engine."):
        return None
    host = sum(v for k, v in idle_by_span(ev).items()
               if k and k.startswith(("scheduler.", "engine."))
               and k != "engine.wait")
    return 100.0 * host / rec.trace["window_s"]
