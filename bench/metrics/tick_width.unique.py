"""Mean true lanes per device call of the engine (``serving/engine.py``;
all chips' lanes together where they are replicas), from its
``coalesce_width_hist`` over the window."""


def read(rec):
    calls = sum(rec.width_hist.values())
    if not calls:
        return None
    return sum(w * c for w, c in rec.width_hist.items()) / calls
