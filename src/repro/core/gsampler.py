"""G-Sampler: the paper's search-based teacher (§4.4.2).

GAMMA [ICCAD'20] extended to the layer-fusion map-space: a domain-specific
genetic algorithm with (i) heuristic seeding (all-sync + the naive uniform
micro-batching strategy of paper §3), (ii) fusion-aware mutation operators
(sync flip, micro-batch grow/shrink), and (iii) a constraint-repair operator
that targets the most over-budget fused group — the domain knowledge that
makes it "several orders of magnitude better" than generic optimizers in
the paper's Table 1.

Population fitness is evaluated by ONE vmapped+jitted cost-model call per
generation (see ``cost_model.evaluate_population``); with the default paper
budget (pop 40 x 50 gens = 2k samples) a search takes well under a second —
that is the vectorized-JAX counterpart of the paper's 0.66-1.3 min search.
"""
from __future__ import annotations

import functools
import time
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..obs import span
from . import cost_model as cm
from .accel import AccelConfig, HwVec, stack_hw, stack_hw_host

__all__ = ["GSamplerConfig", "GSamplerResult", "gsampler_search",
           "naive_uniform_mb", "GridTeacherResult", "gsampler_search_grid"]


@dataclass(frozen=True)
class GSamplerConfig:
    population: int = 40          # paper §5.1
    generations: int = 50         # paper §5.1 (=> 2k samples)
    elite: int = 4
    p_mut_gene: float = 3.0       # expected mutated genes per child
    p_sync_mut: float = 0.25
    repair_tries: int = 6
    seed: int = 0


@dataclass
class GSamplerResult:
    strategy: np.ndarray
    speedup: float
    latency: float
    peak_mem: float
    valid: bool
    n_evals: int
    wall_s: float
    history: list = field(default_factory=list)     # best speedup per gen
    elites: list = field(default_factory=list)      # top-k distinct strategies


def naive_uniform_mb(env, max_mb: int | None = None) -> np.ndarray:
    """Paper §3's naive strategy: one uniform micro-batch for the whole net,
    the largest that stages all intermediates on-chip (binary search)."""
    B = env.batch
    hi = max_mb or B
    best = None
    lo = 1
    while lo <= hi:
        mid = (lo + hi) // 2
        s = np.full(env.nmax, cm.SYNC, dtype=np.int32)
        s[: env.n + 1] = mid
        _, peak, valid = env.speedup(s)
        if valid:
            best, lo = s, mid + 1
        else:
            hi = mid - 1
    if best is None:
        best = np.full(env.nmax, cm.SYNC, dtype=np.int32)
        best[0] = 1
    return best


def _repair_population(env, pop: np.ndarray, cfg: GSamplerConfig,
                       rng: np.random.Generator) -> np.ndarray:
    """Constraint repair for a whole brood at once: while any child is over
    budget, split or shrink its worst fused group.

    One vmapped ``cost_model.evaluate_population_stats`` call per repair
    round replaces the pure-Python per-child ``ref_model`` probes (the old
    hot spot: population x repair_tries reference evaluations per
    generation); the returned per-group memory + group-id arrays supply the
    split/shrink targets."""
    s = pop.copy()
    mask = np.asarray(env.wl_np["mask"])
    for _ in range(cfg.repair_tries):
        out, gid, M_g = cm.evaluate_population_stats(
            env.wl, jnp.asarray(s), float(env.batch),
            float(env.budget_bytes), env.hw)
        invalid = ~np.asarray(out.valid)
        if not invalid.any():
            break
        gid = np.asarray(gid)
        M_g = np.asarray(M_g)
        for i in np.where(invalid)[0]:
            worst = int(np.argmax(M_g[i]))
            span = np.where((gid[i] == worst) & mask)[0]
            start, end = int(span[0]), int(span[-1])
            if end > start and rng.random() < 0.5:
                s[i, (start + end) // 2] = cm.SYNC     # split the group
            else:
                seg = s[i, start: end + 1]
                mbs = np.where(seg > 1, seg, 0)
                if mbs.max() > 1:
                    j = start + int(np.argmax(mbs))
                    s[i, j] = max(1, s[i, j] // 2)     # shrink largest stage
                elif end > start:
                    s[i, (start + end) // 2] = cm.SYNC
                # else: single layer already minimal — leave it
    return s


def _fitness(latency: np.ndarray, peak: np.ndarray, budget: float) -> np.ndarray:
    over = np.maximum(0.0, peak / budget - 1.0)
    return np.where(over > 0.0, -1e3 * (1.0 + over) - latency, -latency)


def gsampler_search(env, cfg: GSamplerConfig = GSamplerConfig(),
                    top_k: int = 8) -> GSamplerResult:
    rng = np.random.default_rng(cfg.seed)
    t0 = time.perf_counter()
    P, n, B = cfg.population, env.n, env.batch

    pop = np.stack([cm.random_strategy(rng, n, env.nmax, B, p_sync=0.4)
                    for _ in range(P)])
    pop[0] = np.full(env.nmax, cm.SYNC, dtype=np.int32); pop[0][0] = B
    pop[1] = naive_uniform_mb(env)
    n_evals = 0
    history = []
    seen_elites: dict[bytes, tuple[float, np.ndarray]] = {}

    for gen in range(cfg.generations):
        out = cm.evaluate_population(env.wl, jnp.asarray(pop), float(B),
                                     float(env.budget_bytes), env.hw)
        n_evals += P
        lat = np.asarray(out.latency); peak = np.asarray(out.peak_mem)
        fit = _fitness(lat, peak, env.budget_bytes)
        order = np.argsort(-fit)
        for idx in order[: cfg.elite]:
            if fit[idx] > -1e3:     # valid
                key = pop[idx, : n + 1].tobytes()
                seen_elites[key] = (float(fit[idx]), pop[idx].copy())
        best = order[0]
        history.append(env.baseline_latency / lat[best]
                       if fit[best] > -1e3 else 0.0)

        # --- next generation ---------------------------------------------
        nxt = [pop[i].copy() for i in order[: cfg.elite]]
        ranks = np.empty(P); ranks[order] = np.arange(P)
        p_sel = (P - ranks) / (P * (P + 1) / 2)
        while len(nxt) < P:
            pa, pb = rng.choice(P, size=2, p=p_sel)
            cut = rng.integers(1, n + 1)
            child = np.concatenate([pop[pa][:cut], pop[pb][cut:]])
            # mutation
            for j in range(n + 1):
                if rng.random() < cfg.p_mut_gene / (n + 1):
                    r = rng.random()
                    if j > 0 and r < cfg.p_sync_mut:
                        child[j] = cm.SYNC if child[j] != cm.SYNC \
                            else int(rng.integers(1, B + 1))
                    elif r < 0.6 and child[j] >= 1:
                        child[j] = int(np.clip(
                            child[j] * (2 if rng.random() < 0.5 else 0.5), 1, B))
                    else:
                        child[j] = int(rng.integers(1, B + 1))
            if child[0] < 1:
                child[0] = int(rng.integers(1, B + 1))
            nxt.append(child)
        brood = _repair_population(env, np.stack(nxt[cfg.elite:]), cfg, rng)
        pop = np.concatenate([np.stack(nxt[: cfg.elite]), brood])

    # final evaluation
    out = cm.evaluate_population(env.wl, jnp.asarray(pop), float(B),
                                 float(env.budget_bytes), env.hw)
    n_evals += P
    lat = np.asarray(out.latency); peak = np.asarray(out.peak_mem)
    fit = _fitness(lat, peak, env.budget_bytes)
    best = int(np.argmax(fit))
    for idx in np.argsort(-fit)[: cfg.elite]:
        if fit[idx] > -1e3:
            key = pop[idx, : n + 1].tobytes()
            seen_elites[key] = (float(fit[idx]), pop[idx].copy())

    elites = [s for _, s in sorted(seen_elites.values(),
                                   key=lambda kv: -kv[0])][:top_k]
    wall = time.perf_counter() - t0
    return GSamplerResult(
        strategy=pop[best].copy(),
        speedup=env.baseline_latency / float(lat[best]),
        latency=float(lat[best]), peak_mem=float(peak[best]),
        valid=bool(fit[best] > -1e3), n_evals=n_evals, wall_s=wall,
        history=history, elites=elites)


# ---------------------------------------------------------------------------
# Device-resident grid G-Sampler (DESIGN.md §10, §11).
#
# The host GA above searches ONE (workload, batch, budget) condition with one
# vmapped fitness call per generation; a teacher corpus needs a whole grid of
# conditions (paper §4.5.1: several memory budgets per workload, §4.6
# generalization: several workloads — and since §11 several ACCELERATORS).
# ``gsampler_search_grid`` runs every condition's population simultaneously:
# selection, crossover, mutation, the constraint-repair operator and the
# fitness evaluations are all jnp over a [C, POP, P] strategy tensor, so the
# ENTIRE evolutionary search — all conditions x populations x generations —
# is one jitted device program with zero host round trips.  Heterogeneity
# (different layer counts, batches, budgets, and per-condition hardware via
# ``accel.stack_hw``) rides the stacked condition axis; padding positions
# stay SYNC.
# ---------------------------------------------------------------------------


@dataclass
class GridTeacherResult:
    """Top-k elite strategies per condition plus their exact costs."""
    strategies: np.ndarray   # [C, K, P] int32
    latency: np.ndarray      # [C, K]
    peak_mem: np.ndarray     # [C, K]
    speedup: np.ndarray      # [C, K]
    valid: np.ndarray        # [C, K] bool
    history: np.ndarray      # [G, C] best valid speedup per generation
    baseline_latency: np.ndarray   # [C]
    repair_rounds: np.ndarray      # [G] repair rounds run per generation
    n_evals: int             # exact cost evaluations, over all conditions
    wall_s: float


# host seconds and count of ``gsampler_search_grid``'s spans (``obs.span``)
spans: dict = {}
# binary-search steps of the naive uniform seed (``_naive_uniform_grid``)
SEED_ITERS = 18


def _randint_1_to_B(key, shape, B) -> jax.Array:
    """Uniform int in [1, B] with per-condition (broadcast) B."""
    u = jax.random.uniform(key, shape)
    return (1.0 + jnp.floor(u * B)).astype(jnp.int32)


def _fitness_jnp(latency, peak, budget):
    over = jnp.maximum(0.0, peak / budget - 1.0)
    return jnp.where(over > 0.0, -1e3 * (1.0 + over) - latency, -latency)


def _naive_uniform_grid(wls, batches, budgets, hw, iters: int = SEED_ITERS,
                        evaluator: str = "xla"):
    """Device twin of :func:`naive_uniform_mb`: per-condition binary search
    for the largest uniform micro-batch that stages everything on-chip."""
    C, P = wls["A"].shape
    n = wls["n"]
    pos = jnp.arange(P)
    valid_pos = pos[None, :] <= n[:, None]

    def uniform(mb):
        return jnp.where(valid_pos, mb[:, None], cm.SYNC).astype(jnp.int32)

    fallback = jnp.where(pos[None, :] == 0, 1, cm.SYNC).astype(jnp.int32)
    fallback = jnp.broadcast_to(fallback, (C, P))
    lo = jnp.ones((C,), jnp.int32)
    hi = batches.astype(jnp.int32)

    def body(_, carry):
        lo, hi, best = carry
        done = lo > hi
        mid = jnp.maximum((lo + hi) // 2, 1)
        s = uniform(mid)
        out = cm.evaluate_grid(wls, s[:, None, :], batches, budgets, hw,
                               evaluator=evaluator)
        ok = out.valid[:, 0] & ~done
        best = jnp.where(ok[:, None], s, best)
        lo = jnp.where(done, lo, jnp.where(ok, mid + 1, lo))
        hi = jnp.where(done, hi, jnp.where(ok, hi, mid - 1))
        return lo, hi, best

    _, _, best = jax.lax.fori_loop(0, iters, body, (lo, hi, fallback))
    return best


def _mutate_grid(key, child, valid_pos, n, B, cfg: GSamplerConfig):
    """Fusion-aware mutation, vectorized over [C, K, P] children."""
    C, K, P = child.shape
    pos = jnp.arange(P)
    k1, k2, k3, k4, k5 = jax.random.split(key, 5)
    p_gene = cfg.p_mut_gene / (n.astype(jnp.float32) + 1.0)       # [C]
    mut = (jax.random.uniform(k1, (C, K, P)) < p_gene[:, None, None]) \
        & valid_pos[:, None, :]
    r = jax.random.uniform(k2, (C, K, P))
    rand_val = _randint_1_to_B(k3, (C, K, P), B[:, None, None])
    sync_flip = (pos[None, None, :] > 0) & (r < cfg.p_sync_mut)
    flipped = jnp.where(child != cm.SYNC, cm.SYNC, rand_val)
    grow = jax.random.uniform(k4, (C, K, P)) < 0.5
    scaled = jnp.clip(jnp.where(grow, child * 2, child // 2),
                      1, B[:, None, None].astype(jnp.int32))
    scale_ok = (r < 0.6) & (child >= 1)
    new = jnp.where(sync_flip, flipped,
                    jnp.where(scale_ok, scaled, rand_val))
    child = jnp.where(mut, new, child)
    # the input micro-batch (position 0) can never sync
    c0 = child[..., 0]
    child = child.at[..., 0].set(
        jnp.where(c0 < 1, _randint_1_to_B(k5, (C, K), B[:, None]), c0))
    return child


def _repair_grid(key, wls, brood, batches, budgets, hw, cfg: GSamplerConfig,
                 evaluator: str = "xla"):
    """Constraint repair for every condition's brood at once: while a child
    is over budget, split its worst fused group or shrink that group's
    largest staged micro-batch — the same operator as
    :func:`_repair_population`, with the span/argmax logic in jnp.
    Returns the repaired brood and the number of rounds run (one
    ``evaluate_grid_stats`` call each), under the name scope ``repair``."""
    C, K, P = brood.shape
    pos = jnp.arange(P)
    mask = wls["mask"]                                            # [C, P]

    def cond_fn(carry):
        # early exit once the whole brood is within budget (the host GA's
        # `break`): evaluate_grid_stats is the GA's hottest call and most
        # late-generation rounds need zero repair
        _, _, i, pending = carry
        return (i < cfg.repair_tries) & pending

    def round_fn(carry):
        s, key, i, _ = carry
        key, kc = jax.random.split(key)
        out, gid, M_g = cm.evaluate_grid_stats(wls, s, batches, budgets, hw,
                                               evaluator=evaluator)
        invalid = ~out.valid                                      # [C, K]
        worst = jnp.argmax(M_g, axis=-1)                          # [C, K]
        members = (gid == worst[..., None]) & mask[:, None, :]    # [C, K, P]
        start = jnp.argmax(members, axis=-1)
        end = P - 1 - jnp.argmax(members[..., ::-1], axis=-1)
        mid = (start + end) // 2
        multi = end > start
        seg_mb = jnp.where(members & (s > 1), s, 0)
        jmax = jnp.argmax(seg_mb, axis=-1)
        has_mb = jnp.max(seg_mb, axis=-1) > 1
        onehot_mid = pos[None, None, :] == mid[..., None]
        onehot_j = pos[None, None, :] == jmax[..., None]
        split_s = jnp.where(onehot_mid, cm.SYNC, s)               # split group
        shrink_s = jnp.where(onehot_j, jnp.maximum(1, s // 2), s)  # halve stage
        alt_s = jnp.where(multi[..., None] & onehot_mid, cm.SYNC, s)
        shr = jnp.where(has_mb[..., None], shrink_s, alt_s)
        do_split = multi & (jax.random.uniform(kc, (C, K)) < 0.5)
        new = jnp.where(do_split[..., None], split_s, shr)
        apply = invalid & members.any(-1)
        s = jnp.where(apply[..., None], new, s)
        return s, key, i + 1, invalid.any()

    with jax.named_scope("repair"):
        s, _, rounds, _ = jax.lax.while_loop(
            cond_fn, round_fn, (brood, key, jnp.int32(0), jnp.bool_(True)))
    return s, rounds


@functools.partial(jax.jit, static_argnames=("cfg", "top_k", "evaluator"))
def _ga_grid(key, wls, batches, budgets, hw,
             cfg: GSamplerConfig, top_k: int, evaluator: str = "xla"):
    """The whole grid GA as one device program.  Returns stacked elites
    [C, top_k, P] with exact costs, plus the best-valid-speedup history
    and the repair rounds of each generation.

    ``evaluator`` selects the fitness/repair backend (DESIGN §13); the
    backends are bit-identical, so the evolved populations — and therefore
    the emitted corpus — do not depend on the choice."""
    C, P = wls["A"].shape
    POP, E = cfg.population, cfg.elite
    n = wls["n"]
    pos = jnp.arange(P)
    valid_pos = pos[None, :] <= n[:, None]
    B = batches.astype(jnp.float32)
    base = cm.baseline_grid(wls, batches, hw).latency             # [C]

    key, k_init, k_sync = jax.random.split(key, 3)
    vals = _randint_1_to_B(k_init, (C, POP, P), B[:, None, None])
    syncs = jax.random.uniform(k_sync, (C, POP, P)) < 0.4
    syncs = syncs.at[:, :, 0].set(False)
    pop = jnp.where(syncs, cm.SYNC, vals)
    pop = jnp.where(valid_pos[:, None, :], pop, cm.SYNC)
    allsync = jnp.where(pos[None, :] == 0,
                        B[:, None].astype(jnp.int32), cm.SYNC)
    pop = pop.at[:, 0, :].set(allsync)
    pop = pop.at[:, 1, :].set(_naive_uniform_grid(wls, batches, budgets, hw,
                                                  evaluator=evaluator))

    def gen(pop, key):
        out = cm.evaluate_grid(wls, pop, batches, budgets, hw,
                               evaluator=evaluator)               # [C, POP]
        fit = _fitness_jnp(out.latency, out.peak_mem, budgets[:, None])
        order = jnp.argsort(-fit, axis=1)
        elites = jnp.take_along_axis(pop, order[:, :E, None], axis=1)
        ranks = jnp.argsort(order, axis=1)
        p_sel = (POP - ranks).astype(jnp.float32) / (POP * (POP + 1) / 2)
        kp, kc, km, kr = jax.random.split(key, 4)
        num = POP - E
        parents = jax.random.categorical(
            kp, jnp.log(p_sel)[:, None, None, :], shape=(C, num, 2))
        pa = jnp.take_along_axis(pop, parents[..., 0][..., None], axis=1)
        pb = jnp.take_along_axis(pop, parents[..., 1][..., None], axis=1)
        cut = 1 + jnp.floor(jax.random.uniform(kc, (C, num))
                            * n[:, None]).astype(jnp.int32)
        child = jnp.where(pos[None, None, :] < cut[..., None], pa, pb)
        child = _mutate_grid(km, child, valid_pos, n, B, cfg)
        brood, rounds = _repair_grid(kr, wls, child, batches, budgets, hw,
                                     cfg, evaluator=evaluator)
        new_pop = jnp.concatenate([elites, brood], axis=1)
        sp = base[:, None] / jnp.maximum(out.latency, 1e-12)
        best = jnp.max(jnp.where(out.valid, sp, 0.0), axis=1)
        return new_pop, (best, rounds)

    key, k_scan = jax.random.split(key)
    pop, (history, repair_rounds) = jax.lax.scan(
        gen, pop, jax.random.split(k_scan, cfg.generations))

    out = cm.evaluate_grid(wls, pop, batches, budgets, hw,
                           evaluator=evaluator)
    fit = _fitness_jnp(out.latency, out.peak_mem, budgets[:, None])
    order = jnp.argsort(-fit, axis=1)[:, :top_k]
    take = lambda x: jnp.take_along_axis(x, order, axis=1)
    strategies = jnp.take_along_axis(pop, order[..., None], axis=1)
    lat, peak = take(out.latency), take(out.peak_mem)
    return dict(strategies=strategies, latency=lat, peak_mem=peak,
                valid=take(out.valid) & (take(fit) > -1e3),
                speedup=base[:, None] / jnp.maximum(lat, 1e-12),
                history=history, baseline_latency=base,
                repair_rounds=repair_rounds)


def _prepare_grid(workloads, hw, C: int, nmax: int, packed):
    """The grid front door's packing contract: host ``AccelConfig``s pack
    on the host, as numpy, so the jitted program's argument transfer moves
    each leaf once; an already-vectorized ``hw`` requires ``packed=``.
    Returns (stacked workloads, per-condition ``HwVec``)."""
    if isinstance(hw, AccelConfig) or (
            isinstance(hw, (list, tuple)) and not isinstance(hw, HwVec)):
        hws = list(hw) if isinstance(hw, (list, tuple)) else [hw] * C
        if len(hws) != C:
            raise ValueError(f"got {len(hws)} accelerators for {C} "
                             "conditions")
        if packed is None:
            if workloads is None:
                raise ValueError("pass workloads= or packed=")
            packed = cm.pack_grid_host(workloads, hws, nmax)
        return packed, stack_hw_host(hws)
    # already-vectorized hardware (stacked HwVec / raw [C, F] array):
    # packing needs host AccelConfigs, so the caller must supply it
    if packed is None:
        raise ValueError("vectorized hw (HwVec / raw array) requires "
                         "`packed=` — pack_workload needs AccelConfigs")
    return packed, stack_hw(hw, C)


def gsampler_search_grid(workloads: list, hw, batches,
                         budgets_bytes, *, nmax: int = 64,
                         cfg: GSamplerConfig = GSamplerConfig(),
                         top_k: int = 8, packed=None,
                         evaluator: str | None = None) -> GridTeacherResult:
    """Search every (workload[c], accel[c], batches[c], budgets_bytes[c])
    condition in one fused device program (the teacher-corpus front door,
    DESIGN §10/§11).

    ``workloads`` entries may repeat (one per memory condition); all
    sequences must have equal length C.  ``hw`` is one ``AccelConfig`` or a
    length-C sequence of them (the §11 hardware axis); an
    already-vectorized form (stacked ``HwVec`` / raw ``[C, F]`` array) is
    accepted too but then ``packed`` is REQUIRED, since packing needs host
    configs.  ``packed`` optionally supplies the ``stack_workloads`` dict
    for the same grid (the corpus pipeline reuses one packing for search
    and decoration); when per-condition accelerators differ, each condition
    must be packed with its own accelerator.  Deterministic for a fixed
    ``cfg.seed`` — the corpus-generation determinism tests rely on it —
    and INDEPENDENT of ``evaluator`` ("xla" | "pallas" | None = the
    ``cost_model`` default): the two fitness backends are bit-identical
    (DESIGN §13), so the same seed yields the same result either way."""
    assert len(workloads) == len(batches) == len(budgets_bytes)
    t0 = time.perf_counter()
    C = len(workloads)
    with span("gsampler.pack", spans):
        wls, hwv = _prepare_grid(workloads, hw, C, nmax, packed)
        batches = np.asarray(batches, np.float32)
        budgets = np.asarray(budgets_bytes, np.float32)
        key = jax.random.PRNGKey(cfg.seed)
    with span("gsampler.dispatch", spans):
        out = _ga_grid(key, wls, batches, budgets, hwv, cfg, top_k,
                       cm._resolve_evaluator(evaluator))
    with span("gsampler.wait", spans):
        out = jax.block_until_ready(out)
    with span("gsampler.unpack", spans) as sp:
        out = {k: np.asarray(v) for k, v in out.items()}
        rounds = int(out["repair_rounds"].sum())
        sp.set_metadata(repair_rounds=rounds, generations=cfg.generations)
    # every evaluation, per condition: the seed's binary search, each
    # generation's population and its repair rounds over the brood, and
    # the final population
    n_evals = C * (SEED_ITERS + cfg.population * (cfg.generations + 1)
                   + rounds * (cfg.population - cfg.elite))
    return GridTeacherResult(
        strategies=out["strategies"], latency=out["latency"],
        peak_mem=out["peak_mem"], speedup=out["speedup"],
        valid=out["valid"], history=out["history"],
        baseline_latency=out["baseline_latency"],
        repair_rounds=out["repair_rounds"], n_evals=n_evals,
        wall_s=time.perf_counter() - t0)
