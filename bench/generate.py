"""The one traffic generator.  A traffic mix is a JSON file under
``bench/traffic/`` whose ``loop`` says how it is offered:

- ``closed``: ``callers`` clients, each with one request outstanding.  The
  conditions come in blocks that hold every (network, accelerator, batch)
  of the mix once, in an order drawn from the seed, each with a budget
  drawn uniformly from ``budget_mb``; so every seed offers the same mix.
- ``open``: arrivals at ``rate_rps`` in bursts of Zipf(``burst_zipf``)
  size capped at ``burst_cap`` with exponential gaps.  A share
  ``1 - unseen_share`` of requests is drawn Zipf(``zipf``) from a fixed
  condition grid, whose popularity order is fixed by ``popularity_seed``
  alone; the rest are unseen conditions with a budget drawn uniformly
  from ``unseen_budget_mb``.
- ``search``: back-to-back search calls, each over every (network,
  accelerator) of the mix times ``budgets_per_condition`` budgets drawn
  uniformly from ``budget_mb``, at ``batch``.

The zipf stream and the burst arrivals follow ``make_stream`` and
``make_arrivals`` of ``benchmarks/bench_serving.py``.
"""
from __future__ import annotations

import json
import pathlib
from dataclasses import dataclass

import numpy as np

MB = float(2 ** 20)
TRAFFIC_DIR = pathlib.Path(__file__).resolve().parent / "traffic"


@dataclass(frozen=True)
class Condition:
    network: str          # name in the program's CNN zoo
    accel: str            # name in the program's accelerator zoo
    batch: int
    budget_bytes: float


def load(name: str) -> dict:
    return json.loads((TRAFFIC_DIR / f"{name}.json").read_text())


def closed_conditions(mix: dict, rng: np.random.Generator):
    """Endless condition stream of a closed-loop mix."""
    combos = [(w, a, b) for w in mix["networks"] for a in mix["accels"]
              for b in mix["batches"]]
    lo, hi = mix["budget_mb"]
    while True:
        for i in rng.permutation(len(combos)):
            w, a, b = combos[i]
            yield Condition(w, a, int(b), float(rng.uniform(lo, hi) * MB))


def grid(mix: dict) -> list[Condition]:
    """The fixed condition grid of an open-loop mix."""
    g = mix["grid"]
    lo, hi, k = g["budget_mb_linspace"]
    budgets = np.linspace(lo, hi, int(k)) * MB
    return [Condition(w, a, int(b), float(m)) for w in g["networks"]
            for a in g["accels"] for b in g["batches"] for m in budgets]


def arrivals(n: int, rate_rps: float, rng: np.random.Generator,
             burst_zipf: float, burst_cap: int) -> np.ndarray:
    """Due times (s) of n requests: capped Zipf bursts, exponential gaps."""
    t, out = 0.0, []
    while len(out) < n:
        burst = min(int(rng.zipf(burst_zipf)), burst_cap)
        out.extend([t] * min(burst, n - len(out)))
        t += float(rng.exponential(burst / rate_rps))
    return np.asarray(out)


def open_stream(mix: dict, rng: np.random.Generator, seconds: float
                ) -> list[tuple[float, Condition, bool]]:
    """(due s, condition, unseen) for every request due in ``seconds``."""
    n = int(mix["rate_rps"] * seconds * 1.5) + 16
    due = arrivals(n, mix["rate_rps"], rng, mix["burst_zipf"],
                   int(mix["burst_cap"]))
    n = int(np.searchsorted(due, seconds))
    g = grid(mix)
    p = 1.0 / np.arange(1, len(g) + 1) ** mix["zipf"]
    p /= p.sum()
    popularity = np.random.default_rng(mix["popularity_seed"]).permutation(
        len(g))
    idx = popularity[rng.choice(len(g), size=n, p=p)]
    unseen = rng.random(n) < mix["unseen_share"]
    gm = mix["grid"]
    lo, hi = mix["unseen_budget_mb"]
    out = []
    for i in range(n):
        if unseen[i]:
            c = Condition(str(rng.choice(gm["networks"])),
                          str(rng.choice(gm["accels"])),
                          int(rng.choice(gm["batches"])),
                          float(rng.uniform(lo, hi) * MB))
        else:
            c = g[idx[i]]
        out.append((float(due[i]), c, bool(unseen[i])))
    return out


def search_calls(mix: dict, rng: np.random.Generator):
    """Endless stream of search calls, each a list of conditions."""
    lo, hi = mix["budget_mb"]
    k = int(mix["budgets_per_condition"])
    while True:
        yield [Condition(w, a, int(mix["batch"]),
                         float(rng.uniform(lo, hi) * MB))
               for w in mix["networks"] for a in mix["accels"]
               for _ in range(k)]
