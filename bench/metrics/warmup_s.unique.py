"""Host seconds of the engine's warmup (``serving/engine.py``
``engine.warmup``: every (nmax bucket, lane count) rollout program traced,
compiled or read from the cache, and run once), part of set-up."""


def read(rec):
    spans = (getattr(rec, "stats0", None) or {}).get("spans") or {}
    warm = spans.get("engine.warmup")
    return warm["seconds"] if warm else None
