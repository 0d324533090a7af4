"""Device self time of the cost evaluator (``core/cost_model.py``
``evaluate_grid`` and ``evaluate_grid_stats``, name scope
``evaluate_grid``; the seed, each generation and each repair round) per
G-Sampler grid call, from the trace."""
from bench.spans import events, scope_self_ms


def read(rec):
    ev = events(rec)
    return None if ev is None else scope_self_ms(ev, "jit__ga_grid",
                                                 "evaluate_grid")
