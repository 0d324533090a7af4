"""Search cells: back-to-back ``gsampler_search_grid`` calls at the
configuration's G-Sampler settings, each over one call's conditions from
``generate``."""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from . import check, generate, harness, work
from .harness import log, now, span

SAMPLE_CALLS = 4       # whole calls compared with the reference


def run(spec, seed: int, seconds: float, tracing: bool, t_process: float,
        compiles) -> tuple[dict, dict, SimpleNamespace]:
    """One run of a search cell: (end-to-end metrics, compared numbers,
    record for the per-layer readers)."""
    import repro
    from repro.core.gsampler import gsampler_search_grid
    from repro.workloads import get_workload
    ga = spec.config["search"]
    mix = spec.mix
    rng = np.random.default_rng(seed)
    cfg = repro.GSamplerConfig(seed=int(rng.integers(2 ** 31)),
                               **ga["gsampler"])
    nets = {n: get_workload(n) for n in mix["networks"]}
    accels = {a: repro.ACCEL_ZOO[a] for a in mix["accels"]}

    def call(conds):
        return gsampler_search_grid(
            [nets[c.network] for c in conds],
            [accels[c.accel] for c in conds],
            [c.batch for c in conds], [c.budget_bytes for c in conds],
            nmax=ga["nmax"], cfg=cfg, top_k=ga["top_k"],
            evaluator=ga["evaluator"])

    warm = generate.search_calls(mix, np.random.default_rng([seed, 3]))
    call(next(warm))                                  # compiles the program
    calls = generate.search_calls(mix, rng)
    tracer = harness.Tracer(tracing, seconds)
    c0 = compiles.snapshot()
    done: list = []                                   # (conditions, result)
    t0 = now()
    setup_s = t0 - t_process
    t_end, t_last = t0 + seconds, t0
    while True:
        t = now()
        tracer.tick(t - t0)
        if t >= t_end:
            break
        with span("generate"):
            conds = next(calls)
        with span("search_call"):
            res = call(conds)
        t = now()
        if t <= t_end:
            done.append((conds, res))
            t_last = t
    tracer.stop()
    c1 = compiles.snapshot()
    n_conds = sum(len(c) for c, _ in done)
    log(f"window: {len(done)} whole search calls, {n_conds} conditions; "
        f"compile events in the window {c1[0] - c0[0]} "
        f"({c1[1] - c0[1]:.3f} s), persistent-cache hits {c1[2] - c0[2]}")

    conds0 = done[0][0] if done else next(calls)
    evals = len(conds0) * cfg.population * (cfg.generations + 1)
    rec = SimpleNamespace(
        seconds=seconds, chips=spec.chips, calls=len(done),
        window_s=t_last - t0,
        eval_ops=sum(work.eval_ops(nets[c.network].n) for c in conds0)
        * cfg.population * (cfg.generations + 1),
        eval_bytes=sum(work.eval_bytes(nets[c.network].n) for c in conds0)
        * cfg.population * (cfg.generations + 1),
        evals_per_call=evals, trace=None)
    rec.device = harness.device_info(harness.require_chips(spec.chips))
    rec.trace = tracer.summary()

    pick = np.random.default_rng([seed, 2])
    samples = []
    for j in pick.permutation(len(done))[:SAMPLE_CALLS]:
        conds, res = done[j]
        for c, cond in enumerate(conds):
            req = repro.MapRequest(nets[cond.network], cond.batch,
                                   cond.budget_bytes, accels[cond.accel])
            samples.append((req, res.strategies[c], res.latency[c],
                            res.peak_mem[c], res.speedup[c], res.valid[c]))
    t_ref = now()
    numbers = check.check_search(spec.reference, samples,
                                 spec.config["limits"])
    rec.sample = samples
    log(f"reference: {len(samples)} conditions x {ga['top_k']} elites, "
        f"{now() - t_ref:.2f} s")
    e2e = {"search_conds_per_s": (n_conds / (t_last - t0) if done else 0.0),
           "setup_s": setup_s}
    rec.attempted, rec.failed = n_conds, numbers["illegal"]
    return e2e, numbers, rec
