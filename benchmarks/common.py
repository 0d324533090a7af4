"""Shared benchmark utilities: artifact cache, teacher-data collection,
model training wrappers, CSV emission.

CACHING CONTRACT (the reason benchmark reruns are cheap): ``load_or(tag,
builder)`` pickles the builder's result under ``artifacts/bench/<tag>.pkl``
and short-circuits every later call with the same tag.  Teacher corpora
(``teacher_<tag>``) and trained mappers (``dt_<tag>`` / ``s2s_<tag>`` /
``hwgen_<mode>``) are cached this way and SHARED across suites — e.g.
table2 and speed_oneshot reuse one trained mapper.  Tags do not encode the
builder's hyperparameters, so after changing teacher/training semantics
delete ``artifacts/bench/`` (or the specific tag) to force a rebuild; CI
always starts from an empty cache.

QUICK vs FULL: the cache tag must differ between modes whenever the built
artifact differs (the convention is a ``_q``/``_quick`` suffix in the tag),
so a quick CI run never poisons a full local run or vice versa.

CSV: ``emit_csv`` prints the scaffold's ``name,us_per_call,derived`` rows;
``fmt_speedup`` renders invalid (over-budget) results as ``N/A``.

COMPILE CACHE: importing this module points JAX's persistent compilation
cache at ``repro.compile_cache`` (``JAX_COMPILATION_CACHE_DIR`` when set,
else ``.jax_cache/`` in the checkout)."""
from __future__ import annotations

import json
import pathlib
import pickle
import time

import jax
import numpy as np

from repro.compile_cache import use_compile_cache
from repro.core import (PAPER_ACCEL, DTConfig, FusionEnv, S2SConfig,
                        TrainConfig, collect_teacher_data, dt_init, dt_loss,
                        s2s_init, s2s_loss, train_model, merge_datasets)

use_compile_cache()

MB = float(2 ** 20)
ART = pathlib.Path("artifacts/bench")
ART.mkdir(parents=True, exist_ok=True)

TRAIN_BUDGETS = [16.0, 32.0, 48.0, 64.0]          # paper §5.3
DT_STEPS = 400                                     # "full training" unit
DT_BATCH = 16


def cache(name: str):
    return ART / f"{name}.pkl"


def load_or(name: str, builder):
    p = cache(name)
    if p.exists():
        with open(p, "rb") as f:
            return pickle.load(f)
    obj = builder()
    with open(p, "wb") as f:
        pickle.dump(obj, f)
    return obj


def teacher_dataset(workloads, batch, budgets, max_steps, tag, seed=0):
    def build():
        return collect_teacher_data(workloads, PAPER_ACCEL, batch=batch,
                                    budgets_mb=budgets, max_steps=max_steps,
                                    seed=seed)
    return load_or(f"teacher_{tag}", build)


def train_dt(dataset, tag, *, max_steps, steps=DT_STEPS, seed=0,
             init_params=None, lr=3e-4):
    """Train (or fine-tune, via init_params) a DNNFuser model; cached."""
    cfg = DTConfig(max_steps=max_steps)

    def build():
        params = (init_params if init_params is not None
                  else dt_init(jax.random.PRNGKey(seed), cfg))
        params, log = train_model(
            lambda p, b: dt_loss(p, cfg, b), params, dataset,
            TrainConfig(steps=steps, batch_size=DT_BATCH, lr=lr,
                        warmup=min(50, steps // 5), seed=seed))
        return {"params": jax.device_get(params), "log": log}
    out = load_or(f"dt_{tag}", build)
    return out["params"], cfg, out["log"]


def train_s2s(dataset, tag, *, max_steps, steps=DT_STEPS, seed=0):
    cfg = S2SConfig(max_steps=max_steps)

    def build():
        params = s2s_init(jax.random.PRNGKey(seed), cfg)
        params, log = train_model(
            lambda p, b: s2s_loss(p, cfg, b), params, dataset,
            TrainConfig(steps=steps, batch_size=DT_BATCH, seed=seed))
        return {"params": jax.device_get(params), "log": log}
    out = load_or(f"s2s_{tag}", build)
    return out["params"], cfg, out["log"]


def env_for(workload, batch, budget_mb, max_steps=64):
    return FusionEnv(workload, PAPER_ACCEL, batch=batch,
                     budget_bytes=budget_mb * MB, nmax=max_steps)


def fmt_speedup(speedup, valid):
    return f"{speedup:.2f}" if valid else "N/A"


def emit_csv(rows):
    """rows: list of (name, us_per_call, derived-string)."""
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
