"""Host time per whole G-Sampler call in the traced stretch (``core/
gsampler.py``: its ``gsampler.pack``, ``gsampler.dispatch`` and
``gsampler.unpack`` spans; ``gsampler.wait`` is the device's)."""
from bench.spans import events, host_spans


def read(rec):
    ev = events(rec)
    if ev is None:
        return None
    spans = host_spans(ev, "gsampler.")
    calls = sum(n == "gsampler.unpack" for n, _, _, _ in spans)
    host = sum(e - s for n, s, e, _ in spans if n != "gsampler.wait")
    return host / calls / 1e6 if calls else None
