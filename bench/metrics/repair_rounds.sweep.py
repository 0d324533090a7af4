"""Mean repair rounds per generation of the G-Sampler calls in the traced
stretch (``core/gsampler.py``: ``repair_rounds`` and ``generations`` on
the ``gsampler.unpack`` span)."""
from bench.spans import events, host_spans


def read(rec):
    ev = events(rec)
    if ev is None:
        return None
    meta = [m for _, _, _, m in host_spans(ev, "gsampler.unpack")
            if "repair_rounds" in m]
    gens = sum(m["generations"] for m in meta)
    return sum(m["repair_rounds"] for m in meta) / gens if gens else None
