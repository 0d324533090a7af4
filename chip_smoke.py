"""Chip smoke: drive the mapper's corpus -> train -> serve path once on a TPU.

    python chip_smoke.py             # one chip: every phase below
    python chip_smoke.py --chips 4   # four chips: replica serving and
                                     # data-parallel training only

Run from the root of a checkout, in one process (a chip belongs to the one
process that touched JAX first).  The mapper is the paper's decision
transformer at full width (``DTConfig()``: 3 blocks, 2 heads, d_model 128,
64 trajectory steps) with random initial weights from ``--seed``.  On one
chip:

 1. device check: exits non-zero unless JAX's first device is a TPU;
 2. teacher corpus: ``generate_teacher_corpus`` over networks x
    accelerators x budgets, once on the XLA evaluator and once on the
    compiled Pallas ``fusion_eval`` kernel.  Both corpora must solve the
    same conditions, with per-condition best teacher speedups within
    ``CORPUS_RTOL``; on one random population the two evaluators must give
    the same validity, group count and group ids on every lane and costs
    within ``EVAL_RTOL``.  The chip does not promise bit-identity between
    two different programs;
 3. training: ``train_model`` for ``TRAIN_STEPS`` steps on that corpus;
    every loss must be finite;
 4. serving: ``repro.serve`` warmed on networks that fill every nmax
    bucket, then a mixed stream of requests (networks x accelerators x
    batches x unseen budgets) through the async scheduler.  Every
    response must be a legal strategy whose validity equals an XLA
    re-evaluation, and nothing may compile after warmup;
 5. host against fused: the host loop ``dnnfuser_infer`` re-solves every
    served request; the number of bit-identical lanes is printed and the
    lanes that differ are listed (not a failure: on the chip the two are
    different programs).

With ``--chips 4`` only the multi-chip paths run, each against one device
in the same process: serving through an engine with 4 data-parallel
replicas (validity and strategies equal per lane), and a few train steps
on a 4-device data-parallel mesh (first loss within ``DP_FIRST_RTOL``,
every loss within ``DP_LOSS_RTOL``).

Per-phase times and counts go to earlier lines.  A failed check is
printed and the remaining phases still run; the process then exits
non-zero without a result line (an exception exits at once).  On success
the last line of stdout is ``{"ok": true, "device": {"platform", "kind",
"count"}}``.
The JAX compilation cache is ``JAX_COMPILATION_CACHE_DIR`` when set, else
``.jax_cache/`` in the checkout (``repro.compile_cache``).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench.harness import Compiles, require_chips

ROOT = pathlib.Path(__file__).resolve().parent
MB = float(2 ** 20)

# corpus: small G-Sampler runs over the serving networks
CORPUS_ACCELS = ("edge", "mobile")
CORPUS_BUDGETS_MB = (8.0, 24.0, 64.0)
CORPUS_BATCH = 64
GA = dict(population=32, generations=8, elite=4, repair_tries=4)
EVAL_POP = 256
CORPUS_RTOL = 1e-3       # per-condition best teacher speedup, xla vs pallas
EVAL_RTOL = 1e-5         # evaluator costs on one population, xla vs pallas
# training and serving
TRAIN_STEPS = 20
TRAIN_BATCH = 64
SERVE_REQUESTS = 24
SERVE_ACCELS = ("edge", "mobile", "laptop", "datacenter")
SERVE_BATCHES = (16, 32, 64)
MAX_TICK = 16
# four chips
REPLICAS = 4
DP_STEPS = 8
# the first loss sees the same params on both sides: only the sharded
# forward differs.  Later ones drift: Adam's early steps are close to
# lr * sign(g) per coordinate, so a gradient whose 4-way all-reduce sums in
# another order flips near-zero coordinates by a whole step (1.2e-3 over 8
# steps on a v5e), while a sharding fault moves the losses by far more
DP_FIRST_RTOL = 1e-5
DP_LOSS_RTOL = 1e-2


def log(msg: str) -> None:
    print(msg, flush=True)


class Smoke:
    """One smoke run's record: backend compile events and persistent-cache
    hits (the benchmark's ``Compiles``), per-phase timing, and the checks
    that failed.  A failed check is printed and recorded, the remaining
    phases still run, and ``main`` exits non-zero at the end."""

    def __init__(self):
        self.compiles = Compiles()
        self.failed: list[str] = []

    def snapshot(self) -> tuple:
        return self.compiles.snapshot()

    def check(self, cond: bool, msg: str) -> None:
        if not cond:
            self.failed.append(msg)
            log(f"CHECK FAILED: {msg}")

    @contextlib.contextmanager
    def phase(self, name: str):
        """Times one phase and prints its wall time and compile counts."""
        t0, c0 = time.perf_counter(), self.snapshot()
        yield
        c1 = self.snapshot()
        log(f"[phase] {name}: {time.perf_counter() - t0:.2f} s; backend "
            f"compile events {c1[0] - c0[0]} ({c1[1] - c0[1]:.2f} s), "
            f"of them persistent-cache hits {c1[2] - c0[2]}")


def serve_networks():
    """Networks whose chains fill the engine's nmax buckets (8, 16, 32,
    64): tiny_cnn (6 layers), whisper-base lowered for decode (14),
    vgg16 (16), resnet50 (50) and mobilenet_v2 (53)."""
    from repro.configs import get_config
    from repro.workloads import mobilenet_v2, resnet50, tiny_cnn, vgg16
    from repro.workloads.lm_workloads import lm_workload
    whisper = lm_workload(get_config("whisper_base"), seq_len=448,
                          batch=CORPUS_BATCH, mode="decode")
    return [tiny_cnn(), whisper, vgg16(), resnet50(), mobilenet_v2()]


def mapper(seed: int):
    from repro import DTConfig, HW_FEATURE_DIM, dt_init
    cfg = DTConfig(hw_dim=HW_FEATURE_DIM)
    return cfg, dt_init(jax.random.PRNGKey(seed), cfg)


def teacher_corpus(nets, seed: int, evaluator: str, ga: dict = GA):
    from repro import ACCEL_ZOO, GSamplerConfig, generate_teacher_corpus
    return generate_teacher_corpus(
        nets, [ACCEL_ZOO[a] for a in CORPUS_ACCELS], batch=CORPUS_BATCH,
        budgets_mb=list(CORPUS_BUDGETS_MB), max_steps=64, top_k=4,
        ga_cfg=GSamplerConfig(seed=seed, **ga), seed=seed,
        evaluator=evaluator)


def best_per_condition(ds) -> dict:
    """(network, accel, budget) -> best teacher speedup over its valid
    trajectories; a condition the teacher did not solve is absent."""
    best: dict = {}
    for name, budget, speedup, accel in ds.meta:
        key = (name, accel, budget)
        best[key] = max(best.get(key, 0.0), speedup)
    return best


def compare_corpora(smoke, ds_x, ds_p) -> None:
    bx, bp = best_per_condition(ds_x), best_per_condition(ds_p)
    smoke.check(set(bx) == set(bp),
                f"xla and pallas corpora solve different conditions: only "
                f"xla {sorted(set(bx) - set(bp))}, only pallas "
                f"{sorted(set(bp) - set(bx))}")
    rel = max(abs(bx[k] - bp[k]) / abs(bx[k]) for k in set(bx) & set(bp))
    same = (len(ds_x) == len(ds_p)
            and all(np.array_equal(getattr(ds_x, f), getattr(ds_p, f))
                    for f in ("rtg", "states", "actions", "mask")))
    log(f"corpus xla vs pallas: {len(bx)} conditions solved by both; "
        f"trajectories {len(ds_x)} vs {len(ds_p)}; bit-identical "
        f"corpora: {same}; max rel diff of best speedup {rel:.3e} "
        f"(limit {CORPUS_RTOL})")
    smoke.check(rel <= CORPUS_RTOL, f"best speedups differ by {rel:.3e}")


def compare_evaluators(smoke, nets, seed: int) -> None:
    """Both evaluators on one random population of every corpus
    condition: same validity, group count and group ids per lane, costs
    within EVAL_RTOL."""
    from repro import ACCEL_ZOO
    from repro.core import cost_model as cm
    rng = np.random.default_rng(seed)
    conds = [(w, ACCEL_ZOO[a], b) for w in nets for a in CORPUS_ACCELS
             for b in CORPUS_BUDGETS_MB]
    wls = cm.stack_workloads([cm.pack_workload(w, a, 64)
                              for w, a, _ in conds])
    strats = jnp.asarray(np.stack([
        np.stack([cm.random_strategy(rng, w.n, 64, CORPUS_BATCH)
                  for _ in range(EVAL_POP)]) for w, _, _ in conds]))
    batches = jnp.full((len(conds),), float(CORPUS_BATCH), jnp.float32)
    budgets = jnp.asarray([b * MB for _, _, b in conds], jnp.float32)
    hws = [a for _, a, _ in conds]
    outs = {ev: cm.evaluate_grid_stats(wls, strats, batches, budgets, hws,
                                       evaluator=ev)
            for ev in ("xla", "pallas")}
    (ox, gx, _), (op, gp, _) = outs["xla"], outs["pallas"]
    mask = np.asarray(wls["mask"])[:, None, :]
    valid_eq = np.asarray(ox.valid) == np.asarray(op.valid)
    groups_eq = np.asarray(ox.n_groups) == np.asarray(op.n_groups)
    gid_eq = np.all((np.asarray(gx) == np.asarray(gp)) | ~mask, axis=-1)
    rels = {f: float(np.max(np.abs(np.asarray(getattr(op, f), np.float64)
                                   - np.asarray(getattr(ox, f), np.float64))
                            / np.maximum(np.abs(np.asarray(getattr(ox, f),
                                                           np.float64)),
                                         1e-30)))
            for f in ("latency", "peak_mem", "traffic")}
    lanes = valid_eq.size
    log(f"evaluators xla vs pallas on {len(conds)} conditions x {EVAL_POP} "
        f"strategies: validity equal on {int(valid_eq.sum())}/{lanes} "
        f"lanes, group count on {int(groups_eq.sum())}/{lanes}, group ids "
        f"on {int(gid_eq.sum())}/{lanes}; max rel diff "
        + ", ".join(f"{k} {v:.3e}" for k, v in rels.items())
        + f" (limit {EVAL_RTOL})")
    smoke.check(bool(valid_eq.all() and groups_eq.all() and gid_eq.all()),
                "xla and pallas evaluators disagree on validity or grouping")
    smoke.check(max(rels.values()) <= EVAL_RTOL,
                f"xla and pallas evaluator costs differ: {rels}")


def train(params, cfg, ds, steps: int, mesh=None):
    from repro import TrainConfig, dt_loss, train_model
    return train_model(lambda p, b: dt_loss(p, cfg, b), params, ds,
                       TrainConfig(steps=steps, batch_size=TRAIN_BATCH,
                                   warmup=min(5, steps), log_every=1),
                       mesh=mesh)


def copy_tree(tree):
    """Fresh buffers: the train step donates the params it is given."""
    return jax.tree.map(lambda x: jnp.array(x, copy=True), tree)


def serve_requests(nets, seed: int):
    from repro import ACCEL_ZOO, MapRequest
    rng = np.random.default_rng(seed)
    # budgets drawn from a continuum, so none is a corpus budget
    return [MapRequest(nets[i % len(nets)],
                       int(rng.choice(SERVE_BATCHES)),
                       float(rng.uniform(4.0, 80.0) * MB),
                       ACCEL_ZOO[str(rng.choice(SERVE_ACCELS))])
            for i in range(SERVE_REQUESTS)]


def build_stack(params, cfg, nets, replicas=None):
    import repro
    config = repro.ServingConfig(max_coalesce=MAX_TICK, max_wave=MAX_TICK,
                                 replicas=replicas)
    return repro.serve(params, cfg, config, warm=nets,
                       accel=repro.ACCEL_ZOO["edge"])


def run_stream(sched, requests):
    arrivals = [i * 1e-3 for i in range(len(requests))]
    return sched.serve_stream(requests, arrivals)


def bucket_of(engine, req) -> int:
    from repro.serving.bucketing import nmax_bucket
    return nmax_bucket(req.workload.n + 1, engine.nmax_buckets)


def check_responses(smoke, engine, requests, responses) -> None:
    """Legal strategies, and validity equal to an XLA re-evaluation of the
    served strategy under the request's own condition."""
    from repro.core import cost_model as cm
    for req, resp in zip(requests, responses):
        n, s = req.workload.n, np.asarray(resp.strategy)
        smoke.check(s.shape == (n + 1,), f"{req.workload.name}: strategy "
                    f"length {s.shape} != {n + 1}")
        legal = (s == cm.SYNC) | ((s >= 1) & (s <= req.batch))
        smoke.check(bool(legal.all()) and s[0] >= 1,
                    f"{req.workload.name}: illegal strategy {s.tolist()}")
        if s.shape != (n + 1,):
            continue
        nb = bucket_of(engine, req)
        full = np.full(nb, cm.SYNC, np.int32)
        full[: n + 1] = s
        out = cm.evaluate(cm.pack_workload(req.workload, req.accel, nb),
                          jnp.asarray(full), float(req.batch),
                          float(req.budget_bytes), req.accel)
        smoke.check(bool(out.valid) == bool(resp.valid),
                    f"{req.workload.name}: served valid={resp.valid} but "
                    f"the XLA evaluator says {bool(out.valid)}")


def host_vs_fused(params, cfg, engine, requests, responses) -> None:
    from repro import dnnfuser_infer
    from repro.core import FusionEnv
    same, differ = 0, []
    for i, (req, resp) in enumerate(zip(requests, responses)):
        env = FusionEnv(req.workload, req.accel, batch=req.batch,
                        budget_bytes=req.budget_bytes,
                        nmax=bucket_of(engine, req))
        host = np.asarray(dnnfuser_infer(params, cfg, env).strategy)
        host = host[: req.workload.n + 1]
        if np.array_equal(host, resp.strategy):
            same += 1
        else:
            first = int(np.argmax(host != resp.strategy))
            differ.append(f"lane {i} {req.workload.name}/{req.accel.name}/"
                          f"b{req.batch}/{req.budget_bytes / MB:.2f}MB: "
                          f"first differs at position {first} (host "
                          f"{int(host[first])}, fused "
                          f"{int(resp.strategy[first])})")
    log(f"host vs fused: {same}/{len(requests)} lanes bit-identical")
    for d in differ:
        log(f"  differs: {d}")


def one_chip(smoke: Smoke, seed: int) -> None:
    nets = serve_networks()
    cfg, params = mapper(seed)

    with smoke.phase("corpus (xla evaluator)"):
        ds_x = teacher_corpus(nets, seed, "xla")
    with smoke.phase("corpus (pallas evaluator, compiled)"):
        ds_p = teacher_corpus(nets, seed, "pallas")
    compare_corpora(smoke, ds_x, ds_p)
    with smoke.phase("evaluator parity"):
        compare_evaluators(smoke, nets, seed)

    with smoke.phase("train"):
        params, tlog = train(params, cfg, ds_x, TRAIN_STEPS)
    losses = [loss for _, loss in tlog["losses"]]
    log(f"train: {len(losses)} losses logged, first {losses[0]:.6f}, last "
        f"{losses[-1]:.6f}")
    smoke.check(len(losses) == TRAIN_STEPS
                and bool(np.all(np.isfinite(losses))),
                f"non-finite or missing training losses: {losses}")

    with smoke.phase("serve warmup"):
        sched = build_stack(params, cfg, nets)
    engine = sched.engine
    requests = serve_requests(nets, seed)
    compiles = engine.compile_count
    jit_cache = engine_jit_cache_size()
    with smoke.phase("serve stream"):
        responses = run_stream(sched, requests)
    buckets = sorted({bucket_of(engine, r) for r in requests})
    log(f"serve: {len(responses)} requests over nmax buckets {buckets} "
        f"(engine buckets {list(engine.nmax_buckets)}); device calls "
        f"{engine.device_calls}; engine compiles after warmup "
        f"{engine.compile_count - compiles}; rollout jit cache "
        f"{jit_cache} -> {engine_jit_cache_size()}")
    smoke.check(buckets == list(engine.nmax_buckets),
                f"requests reach buckets {buckets}, not all of "
                f"{engine.nmax_buckets}")
    smoke.check(engine.compile_count == compiles
                and engine_jit_cache_size() == jit_cache,
                "the serving stream compiled after warmup")
    with smoke.phase("serve checks"):
        check_responses(smoke, engine, requests, responses)
    log(f"serve: {sum(r.valid for r in responses)}/{len(responses)} "
        f"within budget; legality and validity checked against the XLA "
        f"evaluator")

    with smoke.phase("host vs fused"):
        host_vs_fused(params, cfg, engine, requests, responses)


def engine_jit_cache_size() -> int:
    from repro.core import infer
    return infer._fused_batch._cache_size()


def four_chips(smoke: Smoke, seed: int) -> None:
    from repro.distributed.sharding import data_parallel_mesh
    nets = serve_networks()
    cfg, params = mapper(seed)
    requests = serve_requests(nets, seed)

    with smoke.phase("serve, one device"):
        one = run_stream(build_stack(params, cfg, nets), requests)
    with smoke.phase(f"serve, {REPLICAS} replicas"):
        sched = build_stack(params, cfg, nets, replicas=REPLICAS)
        many = run_stream(sched, requests)
    rep = sched.engine.stats()["replicas"]
    same_valid = sum(a.valid == b.valid for a, b in zip(one, many))
    same_strat = sum(np.array_equal(a.strategy, b.strategy)
                     for a, b in zip(one, many))
    log(f"replicas: devices {rep['devices']}; rows per replica "
        f"{rep['rows_per_replica']}; sharded calls {rep['sharded_calls']}")
    log(f"replicas vs one device: validity equal on {same_valid}/"
        f"{len(requests)} lanes, strategies equal on {same_strat}/"
        f"{len(requests)}")
    for i, (a, b) in enumerate(zip(one, many)):
        if not np.array_equal(a.strategy, b.strategy):
            log(f"  differs: lane {i} {requests[i].workload.name}")
    smoke.check(len(set(rep["devices"])) == REPLICAS
                and min(rep["rows_per_replica"]) > 0,
                f"replica work did not spread over {REPLICAS} devices: {rep}")
    smoke.check(same_valid == len(requests) and same_strat == len(requests),
                "replicated serving differs from one device")

    with smoke.phase("data-parallel setup: small teacher corpus"):
        ds = teacher_corpus(nets[:2], seed, "xla",
                            dict(GA, generations=4))
    with smoke.phase("train, one device"):
        _, log1 = train(copy_tree(params), cfg, ds, DP_STEPS)
    with smoke.phase(f"train, {REPLICAS}-device data-parallel mesh"):
        _, log4 = train(copy_tree(params), cfg, ds, DP_STEPS,
                        mesh=data_parallel_mesh(REPLICAS))
    l1 = np.asarray([v for _, v in log1["losses"]])
    l4 = np.asarray([v for _, v in log4["losses"]])
    rel = np.abs(l4 - l1) / np.abs(l1)
    log(f"data-parallel vs one device: losses {l1.tolist()} vs "
        f"{l4.tolist()}; rel diff of the first {rel[0]:.3e} (limit "
        f"{DP_FIRST_RTOL}), max {rel.max():.3e} (limit {DP_LOSS_RTOL})")
    smoke.check(bool(np.all(np.isfinite(l4))) and rel[0] <= DP_FIRST_RTOL
                and rel.max() <= DP_LOSS_RTOL,
                "data-parallel losses differ from one device")


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    devs = require_chips(args.chips)
    log(f"device: platform={devs[0].platform} kind={devs[0].device_kind} "
        f"count={len(devs)}")
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import use_compile_cache
    log(f"compile cache: {use_compile_cache()}")
    smoke = Smoke()
    t0 = time.perf_counter()
    (one_chip if args.chips == 1 else four_chips)(smoke, args.seed)
    compiles, compile_s, hits = smoke.snapshot()
    log(f"total: {time.perf_counter() - t0:.2f} s; backend compile events "
        f"{compiles} ({compile_s:.2f} s), of them persistent-cache hits "
        f"{hits}")
    if smoke.failed:
        sys.exit(f"chip_smoke: {len(smoke.failed)} check(s) failed: "
                 + "; ".join(smoke.failed))
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(devs)}}), flush=True)


if __name__ == "__main__":
    main()
