"""Warm-started searcher portfolio: pure-JAX DE + diagonal CMA-ES
(DESIGN.md §17).

Two population searchers alongside the G-Sampler GA, built for one job:
ESCALATION.  When the one-shot mapper (or its gradient polish) leaves a
request budget-violating or low-quality, the engine re-searches the
condition — but warm-started from the proposal, so the search spends its
evaluations refining a good incumbent instead of rediscovering it.
Measured in ``benchmarks/bench_polish.py``: the warm-started portfolio
reaches cold-G-Sampler-final cost in a small fraction of the cost
evaluations.

Search space — the ENCODED ACTION space of ``env.encode_action``: a
genome is ``y`` in ``[-1, 1]^P`` where ``y < 0`` decodes to SYNC and
``y >= 0`` to the tile ``clip(round(y * B), 1, B)`` (position 0 and
padding follow the serving rules: the input position cannot sync,
positions past ``n`` always do).  Warm start is therefore exact:
``encode_action(proposal)`` decodes back to the proposal bit-for-bit,
and sync-structure flips stay reachable as sign changes.

Both searchers follow the grid idiom of ``gsampler_search_grid``: every
condition's population evolves simultaneously inside ONE jitted program,
fitness is one ``cost_model.evaluate_grid`` call per generation
(``evaluator`` = "xla" | "pallas", bit-identical backends), and
selection is elitist — the returned strategy can never be worse (by
fitness) than the best warm seed, which includes the proposal itself.

Randomness protocol: every random draw uses a PER-CONDITION key stream,
``fold_in(PRNGKey(cfg.seed), salts[c])`` — so a single-condition run
with ``salts=[c]`` bit-reproduces row ``c`` of a grid run (tested), and
an engine escalating with constant salts stays tick-composition
invariant (§14 determinism).
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from . import cost_model as cm
from .env import encode_action
from .gsampler import _fitness_jnp, _prepare_grid

__all__ = ["PortfolioConfig", "PortfolioResult", "de_search_grid",
           "cmaes_search_grid"]


@dataclass(frozen=True)
class PortfolioConfig:
    """Shared searcher knobs (hashable: static jit argument)."""
    population: int = 24
    generations: int = 30
    seed: int = 0
    warm_sigma: float = 0.12   # genome jitter around a warm proposal
    # -- differential evolution --
    de_f: float = 0.6          # differential weight
    de_cr: float = 0.7         # crossover rate
    # -- diagonal CMA-ES --
    cma_mu: int = 0            # parents (0 -> population // 2)
    cma_lr_sigma: float = 0.2  # per-dim variance adaptation rate
    sigma0: float = 0.4        # cold-start step size
    sigma_min: float = 1e-3
    sigma_max: float = 0.8


@dataclass
class PortfolioResult:
    """Best-ever strategy per condition plus the convergence history."""
    strategies: np.ndarray        # [C, P] int32
    latency: np.ndarray           # [C]
    peak_mem: np.ndarray          # [C]
    speedup: np.ndarray           # [C]
    valid: np.ndarray             # [C] bool
    history: np.ndarray           # [G, C] best valid latency so far (inf)
    baseline_latency: np.ndarray  # [C]
    n_evals: int                  # exact cost evaluations performed
    wall_s: float


def _decode_grid(y: jax.Array, B: jax.Array,
                 valid_pos: jax.Array) -> jax.Array:
    """Genomes [C, POP, P] -> strategies: the serving decode rules.

    Matches ``env.decode_action_jnp`` for ``y >= 0``; position 0 decodes
    its magnitude (the input micro-batch can never sync) and padding
    positions stay SYNC."""
    Bc = B[:, None, None]
    mb = jnp.clip(jnp.round(jnp.abs(y) * Bc), 1.0, Bc)
    s = jnp.where(y < 0.0, float(cm.SYNC), mb)
    s = s.at[..., 0].set(mb[..., 0])
    s = jnp.where(valid_pos[:, None, :], s, float(cm.SYNC))
    return s.astype(jnp.int32)


def _allsync_genome(C: int, P: int) -> jax.Array:
    """The guaranteed-format fallback member: full-batch input, all SYNC
    (the same heuristic seed the GA plants)."""
    y = jnp.full((P,), -0.5, jnp.float32).at[0].set(1.0)
    return jnp.broadcast_to(y, (C, P))


def _vsplit(keys: jax.Array, num: int) -> tuple:
    """Per-condition key split: [C, 2] -> ``num`` arrays of [C, 2]."""
    ks = jax.vmap(lambda k: jax.random.split(k, num))(keys)
    return tuple(ks[:, i] for i in range(num))


@functools.partial(jax.jit,
                   static_argnames=("method", "warm", "cfg", "evaluator"))
def _portfolio_grid_jit(keys, wls, batches, budgets, hw, y0,
                        method: str, warm: bool, cfg: PortfolioConfig,
                        evaluator: str = "xla"):
    C, P = wls["A"].shape
    POP, G = cfg.population, cfg.generations
    n = wls["n"]
    pos = jnp.arange(P)
    valid_pos = pos[None, :] <= n[:, None]
    B = batches.astype(jnp.float32)
    base = cm.baseline_grid(wls, batches, hw).latency

    def fitness(y):
        s = _decode_grid(y, B, valid_pos)
        out = cm.evaluate_grid(wls, s, batches, budgets, hw,
                               evaluator=evaluator)
        fit = _fitness_jnp(out.latency, out.peak_mem, budgets[:, None])
        vlat = jnp.min(jnp.where(out.valid, out.latency, jnp.inf), axis=1)
        return fit, vlat

    def track(best, y, fit, vlat):
        best_fit, best_y, best_lat = best
        idx = jnp.argmax(fit, axis=1)
        top = jnp.take_along_axis(fit, idx[:, None], axis=1)[:, 0]
        upd = top > best_fit
        best_fit = jnp.where(upd, top, best_fit)
        cand = jnp.take_along_axis(y, idx[:, None, None], axis=1)[:, 0]
        best_y = jnp.where(upd[:, None], cand, best_y)
        return best_fit, best_y, jnp.minimum(best_lat, vlat)

    keys, k_init = _vsplit(keys, 2)
    if warm:
        noise = jax.vmap(
            lambda k: jax.random.normal(k, (POP, P)))(k_init)
        pop = jnp.clip(y0[:, None, :] + cfg.warm_sigma * noise, -1.0, 1.0)
        pop = pop.at[:, 0, :].set(y0)          # member 0: the exact proposal
    else:
        pop = jax.vmap(lambda k: jax.random.uniform(
            k, (POP, P), minval=-1.0, maxval=1.0))(k_init)
    pop = pop.at[:, 1, :].set(_allsync_genome(C, P))

    fit, vlat = fitness(pop)
    best = (jnp.full((C,), -jnp.inf), pop[:, 0], jnp.full((C,), jnp.inf))
    best = track(best, pop, fit, vlat)

    if method == "de":
        def gen(carry, _):
            pop, fit, keys, best = carry
            keys, k1, k2, k3 = _vsplit(keys, 4)
            r = jax.vmap(lambda k: jax.random.randint(
                k, (POP, 3), 0, POP))(k1)
            x1 = jnp.take_along_axis(pop, r[..., 0][..., None], axis=1)
            x2 = jnp.take_along_axis(pop, r[..., 1][..., None], axis=1)
            x3 = jnp.take_along_axis(pop, r[..., 2][..., None], axis=1)
            mutant = jnp.clip(x1 + cfg.de_f * (x2 - x3), -1.0, 1.0)
            jrand = jax.vmap(lambda k: jax.random.randint(
                k, (POP,), 0, P))(k2)
            cross = (jax.vmap(lambda k: jax.random.uniform(
                k, (POP, P)))(k3) < cfg.de_cr) \
                | (pos[None, None, :] == jrand[..., None])
            trial = jnp.where(cross, mutant, pop)
            tfit, tvlat = fitness(trial)
            best = track(best, trial, tfit, tvlat)
            sel = tfit >= fit
            pop = jnp.where(sel[..., None], trial, pop)
            fit = jnp.where(sel, tfit, fit)
            return (pop, fit, keys, best), best[2]

        (_, _, _, best), history = jax.lax.scan(
            gen, (pop, fit, keys, best), None, length=G)
    elif method == "cmaes":
        MU = cfg.cma_mu or POP // 2
        w = np.log(MU + 0.5) - np.log(np.arange(1, MU + 1))
        w = jnp.asarray(w / w.sum(), jnp.float32)
        mean = y0 if warm else jnp.zeros((C, P), jnp.float32)
        sigma = jnp.full((C, P),
                         cfg.warm_sigma if warm else cfg.sigma0,
                         jnp.float32)

        def gen(carry, _):
            mean, sigma, keys, best = carry
            keys, k1 = _vsplit(keys, 2)
            z = jax.vmap(lambda k: jax.random.normal(k, (POP, P)))(k1)
            z = z.at[:, 0, :].set(0.0)         # sample 0: the mean itself
            x = jnp.clip(mean[:, None, :] + sigma[:, None, :] * z,
                         -1.0, 1.0)
            xfit, xvlat = fitness(x)
            best = track(best, x, xfit, xvlat)
            order = jnp.argsort(-xfit, axis=1)[:, :MU]
            xsel = jnp.take_along_axis(x, order[..., None], axis=1)
            zsel = jnp.take_along_axis(z, order[..., None], axis=1)
            mean = jnp.sum(w[None, :, None] * xsel, axis=1)
            var_step = jnp.sum(w[None, :, None] * (zsel ** 2 - 1.0),
                               axis=1)
            sigma = jnp.clip(
                sigma * jnp.exp(0.5 * cfg.cma_lr_sigma * var_step),
                cfg.sigma_min, cfg.sigma_max)
            return (mean, sigma, keys, best), best[2]

        (_, _, _, best), history = jax.lax.scan(
            gen, (mean, sigma, keys, best), None, length=G)
    else:
        raise ValueError(f"unknown portfolio method {method!r}")

    _, best_y, _ = best
    best_s = _decode_grid(best_y[:, None, :], B, valid_pos)
    out = cm.evaluate_grid(wls, best_s, batches, budgets, hw,
                           evaluator=evaluator)
    lat = out.latency[:, 0]
    return dict(strategies=best_s[:, 0], latency=lat,
                peak_mem=out.peak_mem[:, 0], valid=out.valid[:, 0],
                speedup=base / jnp.maximum(lat, 1e-12),
                history=history,                 # scan-stacked: [G, C]
                baseline_latency=base)


def _search_grid(method: str, workloads, hw, batches, budgets_bytes, *,
                 nmax, cfg, init_strategies, salts, packed,
                 evaluator) -> PortfolioResult:
    t0 = time.perf_counter()
    batches = np.asarray(batches, np.float32)
    budgets = np.asarray(budgets_bytes, np.float32)
    C = len(batches)
    wls, hwv = _prepare_grid(workloads, hw, C, nmax, packed)
    P = wls["A"].shape[-1]
    if salts is None:
        salts = np.arange(C)
    salts = np.asarray(salts, np.uint32)
    assert salts.shape == (C,)
    key0 = jax.random.PRNGKey(cfg.seed)
    keys = jax.vmap(lambda s: jax.random.fold_in(key0, s))(
        jnp.asarray(salts))
    warm = init_strategies is not None
    if warm:
        init = np.asarray(init_strategies, np.int32)
        assert init.shape == (C, P), (init.shape, (C, P))
        y0 = jnp.asarray(np.stack([
            encode_action(init[c], int(batches[c])) for c in range(C)]))
    else:
        y0 = jnp.zeros((C, P), jnp.float32)
    out = _portfolio_grid_jit(keys, wls, jnp.asarray(batches),
                              jnp.asarray(budgets), hwv, y0, method, warm,
                              cfg, cm._resolve_evaluator(evaluator))
    out = {k: np.asarray(v) for k, v in out.items()}
    hist = out["history"].reshape(cfg.generations, C)
    n_evals = C * cfg.population * (cfg.generations + 1) + C
    return PortfolioResult(
        strategies=out["strategies"], latency=out["latency"],
        peak_mem=out["peak_mem"], speedup=out["speedup"],
        valid=out["valid"], history=hist,
        baseline_latency=out["baseline_latency"], n_evals=n_evals,
        wall_s=time.perf_counter() - t0)


def de_search_grid(workloads, hw, batches, budgets_bytes, *,
                   nmax: int = 64,
                   cfg: PortfolioConfig = PortfolioConfig(),
                   init_strategies=None, salts=None, packed=None,
                   evaluator: str | None = None) -> PortfolioResult:
    """Differential evolution over every condition of the grid in one
    jitted program (rand/1/bin, elitist replacement).

    ``init_strategies`` [C, P] int32 warm-starts the population from a
    proposal per condition (member 0 is the exact proposal; the rest are
    ``warm_sigma`` genome jitters of it) — the DT-propose -> search-refine
    protocol.  ``salts`` [C] picks each condition's RNG stream
    (default ``arange(C)``): a single-condition run with ``salts=[c]``
    bit-reproduces grid row ``c``.  ``history[g, c]`` is the best VALID
    exact latency seen up to generation ``g`` (inf until one exists);
    ``n_evals`` counts exact cost evaluations, the unit the
    warm-vs-cold benchmark gates on."""
    return _search_grid("de", workloads, hw, batches, budgets_bytes,
                        nmax=nmax, cfg=cfg,
                        init_strategies=init_strategies, salts=salts,
                        packed=packed, evaluator=evaluator)


def cmaes_search_grid(workloads, hw, batches, budgets_bytes, *,
                      nmax: int = 64,
                      cfg: PortfolioConfig = PortfolioConfig(),
                      init_strategies=None, salts=None, packed=None,
                      evaluator: str | None = None) -> PortfolioResult:
    """Diagonal (separable) CMA-ES over the same grid contract as
    :func:`de_search_grid`: rank-weighted recombination of the top
    ``cma_mu`` samples, per-dimension variance adaptation, the mean
    re-evaluated every generation (sample 0), best-ever elitism across
    all evaluations.  Warm start sets the initial mean to the proposal
    and the step size to ``warm_sigma``."""
    return _search_grid("cmaes", workloads, hw, batches, budgets_bytes,
                        nmax=nmax, cfg=cfg,
                        init_strategies=init_strategies, salts=salts,
                        packed=packed, evaluator=evaluator)
