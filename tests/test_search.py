"""G-Sampler + baseline searcher behaviour."""
import jax
import numpy as np
import pytest

from repro.core import (FusionEnv, GSamplerConfig, PAPER_ACCEL,
                        BASELINE_METHODS, gsampler_search)
from repro.core.baselines import random_search
from repro.workloads import resnet18, vgg16

MB = 2 ** 20


@pytest.fixture(scope="module")
def env():
    return FusionEnv(resnet18(), PAPER_ACCEL, batch=64,
                     budget_bytes=20 * MB)


def test_gsampler_valid_and_beats_baseline(env):
    res = gsampler_search(env, GSamplerConfig(generations=25, seed=0))
    assert res.valid
    assert res.speedup > 1.0
    assert res.n_evals <= 26 * 40   # sampling budget honored


def test_gsampler_beats_random(env):
    res = gsampler_search(env, GSamplerConfig(generations=25, seed=0))
    rnd = random_search(env, budget=1000, seed=0)
    gs_obj = res.latency if res.valid else np.inf
    rnd_obj = rnd.latency if rnd.valid else np.inf
    assert gs_obj < rnd_obj


def test_gsampler_improves_over_generations(env):
    res = gsampler_search(env, GSamplerConfig(generations=30, seed=1))
    hist = [h for h in res.history if h > 0]
    assert hist and max(hist) >= hist[0]


def test_gsampler_respects_budget_constraint(env):
    for seed in range(3):
        res = gsampler_search(env, GSamplerConfig(generations=15, seed=seed))
        assert res.peak_mem <= env.budget_bytes * (1 + 1e-6)


@pytest.mark.parametrize("method", sorted(BASELINE_METHODS))
def test_baselines_run_within_budget(env, method):
    r = BASELINE_METHODS[method](env, budget=400, seed=0)
    assert r.n_evals <= 400
    assert np.isfinite(r.latency)


def test_elites_are_distinct_and_valid(env):
    res = gsampler_search(env, GSamplerConfig(generations=20, seed=2),
                          top_k=6)
    seen = set()
    for s in res.elites:
        _, peak, valid = env.speedup(s)
        assert valid
        seen.add(s[: env.n + 1].tobytes())
    assert len(seen) == len(res.elites)


# ---------------------------------------------------------------------------
# Evaluator-backend equivalence (DESIGN §13): the grid G-Sampler and the
# teacher-corpus pipeline must be BIT-identical per seed whether fitness
# runs on the XLA evaluator or the Pallas fusion_eval kernel (interpret on
# this CPU container) — the property that makes the backend switch safe to
# flip in production without regenerating a single corpus.
# ---------------------------------------------------------------------------

_BE_CFG = GSamplerConfig(population=8, generations=4, elite=2,
                         repair_tries=2, seed=3)


def _grid_args():
    from repro.core.accel import ACCEL_ZOO
    from repro.workloads import tiny_cnn
    wls = [tiny_cnn(), tiny_cnn()]
    hws = [PAPER_ACCEL, ACCEL_ZOO["datacenter"]]
    return wls, hws, [8.0, 8.0], [2 * MB, 4 * MB]


def test_gsampler_grid_backend_equivalence():
    from repro.core import gsampler_search_grid
    wls, hws, batches, budgets = _grid_args()
    res = {ev: gsampler_search_grid(wls, hws, batches, budgets, nmax=16,
                                    cfg=_BE_CFG, top_k=4, evaluator=ev)
           for ev in ("xla", "pallas")}
    for field in ("strategies", "latency", "peak_mem", "speedup", "valid",
                  "history", "baseline_latency"):
        np.testing.assert_array_equal(getattr(res["xla"], field),
                                      getattr(res["pallas"], field),
                                      err_msg=field)
    assert res["xla"].valid.any()           # the grid actually solved


@pytest.mark.parametrize("budget_mb,rounds", [(1e-3, _BE_CFG.repair_tries),
                                               (1e6, 1)])
def test_repair_rounds_and_exact_n_evals(budget_mb, rounds):
    """Under a budget nothing fits, every generation runs all
    ``repair_tries`` rounds; under one everything fits, the first round
    finds the brood valid and stops.  ``n_evals`` counts exactly: the
    seed's binary search, each generation's population and its repair
    rounds over the brood, and the final population."""
    from repro.core import gsampler_search_grid
    from repro.core.gsampler import SEED_ITERS, spans
    wls, hws, batches, _ = _grid_args()
    cfg = _BE_CFG
    calls = spans.get("gsampler.wait", {}).get("count", 0)
    res = gsampler_search_grid(wls, hws, batches, [budget_mb * MB] * 2,
                               nmax=16, cfg=cfg, top_k=4)
    for name in ("gsampler.pack", "gsampler.dispatch", "gsampler.unpack"):
        assert spans[name]["count"] >= 1, name
    assert spans["gsampler.wait"]["count"] == calls + 1
    assert res.repair_rounds.shape == (cfg.generations,)
    assert (res.repair_rounds == rounds).all()
    assert res.n_evals == 2 * (SEED_ITERS
                               + cfg.population * (cfg.generations + 1)
                               + cfg.generations * rounds
                               * (cfg.population - cfg.elite))


def test_grid_host_pack_equals_device_pack():
    """A grid of host ``AccelConfig``s is packed on the host; the search
    must return exactly what the same call given the device-packed
    ``packed=`` dict and a stacked ``HwVec`` returns, and the numpy and
    device arguments must share one compiled program."""
    from repro.core import cost_model as cm, gsampler_search_grid
    from repro.core.accel import ACCEL_ZOO, stack_hw
    from repro.workloads import tiny_cnn
    wls = [tiny_cnn(), resnet18(), tiny_cnn(), resnet18()]
    hws = [ACCEL_ZOO["edge"], ACCEL_ZOO["edge"], ACCEL_ZOO["datacenter"],
           ACCEL_ZOO["datacenter"]]
    batches, budgets = [8.0, 16.0, 8.0, 16.0], [2 * MB, 8 * MB, 4 * MB,
                                                16 * MB]
    kw = dict(nmax=32, cfg=_BE_CFG, top_k=4, evaluator="xla")
    host = gsampler_search_grid(wls, hws, batches, budgets, **kw)
    packed = cm.stack_workloads([cm.pack_workload(w, h, 32)
                                 for w, h in zip(wls, hws)])
    hwv = stack_hw(hws, len(hws))
    compiles = []

    def on_compile(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(event)

    jax.monitoring.register_event_duration_secs_listener(on_compile)
    try:
        dev = gsampler_search_grid(wls, hwv, batches, budgets,
                                   packed=packed, **kw)
    finally:
        jax.monitoring.unregister_event_duration_listener(on_compile)
    assert compiles == []
    for field in ("strategies", "latency", "peak_mem", "speedup", "valid",
                  "history", "baseline_latency", "repair_rounds"):
        np.testing.assert_array_equal(getattr(host, field),
                                      getattr(dev, field), err_msg=field)
    assert host.n_evals == dev.n_evals
    assert host.valid.any()


def test_teacher_corpus_backend_equivalence():
    from repro.core.accel import ACCEL_ZOO
    from repro.core.dataset import generate_teacher_corpus
    from repro.workloads import tiny_cnn
    ds = {ev: generate_teacher_corpus(
              [tiny_cnn()], [PAPER_ACCEL, ACCEL_ZOO["datacenter"]], batch=8,
              budgets_mb=[2.0], max_steps=16, top_k=4, ga_cfg=_BE_CFG,
              seed=5, evaluator=ev)
          for ev in ("xla", "pallas")}
    for field in ("rtg", "states", "actions", "mask", "t0", "hw"):
        np.testing.assert_array_equal(getattr(ds["xla"], field),
                                      getattr(ds["pallas"], field),
                                      err_msg=field)
    assert ds["xla"].meta == ds["pallas"].meta
    assert len(ds["xla"]) > 0


# ---------------------------------------------------------------------------
# optimality lower bound (DESIGN §16): the certified exact optimum bounds
# the entire search stack from below — a G-Sampler "improvement" past it
# would mean the evaluator and the search disagree about the map-space.
# ---------------------------------------------------------------------------

import _adversarial as adv
from repro.core import optimal as op
from repro.core.accel import ACCEL_ZOO


@pytest.mark.parametrize(
    "case", [c for c in adv.cases() if c[4] is c[5]], ids=lambda c: c[0])
def test_gsampler_never_below_certified_optimum(case):
    name, wl, batch, budget, pack_hw, serve_hw = case
    env = FusionEnv(wl, serve_hw, batch=batch, budget_bytes=budget,
                    nmax=adv.NMAX)
    opt = op.optimal_mapping(env, certify=False)
    res = gsampler_search(env, GSamplerConfig(generations=10,
                                              population=128, seed=0))
    if not opt.valid:
        assert not res.valid, (name, "GA found a mapping the oracle proved "
                               "infeasible")
        return
    if res.valid:
        # f32 search latency vs f64 optimum: float tolerance only
        assert float(res.latency) >= opt.latency * (1 - 1e-4), \
            (name, float(res.latency), opt.latency)


def test_gsampler_reaches_optimum_on_tiny_chain():
    """On a 3-layer chain a budgeted GA should actually FIND the optimum —
    the bound above is tight, not vacuous."""
    wl = adv.mixed_magnitude()
    env = FusionEnv(wl, ACCEL_ZOO["edge"], batch=16,
                    budget_bytes=24 * adv.MB, nmax=adv.NMAX)
    opt = op.optimal_mapping(env, certify=False)
    res = gsampler_search(env, GSamplerConfig(generations=30,
                                              population=256, seed=0))
    assert res.valid and opt.valid
    assert float(res.latency) <= opt.latency * (1 + 1e-4)
