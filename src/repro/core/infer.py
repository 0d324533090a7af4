"""One-shot inference: the paper's headline capability (§4.5.2).

The trained model is rolled out autoregressively against the cost-model
environment: at step t it reads the (reward, state, action) prefix — with
the conditioning reward supplied by the requested memory budget — and emits
micro-batch a_t; the environment updates s_{t+1}/r_{t+1}.  One rollout
(= N+1 tiny forward passes) replaces an entire 2k-sample search, which is
the 66x-127x speed claim benchmarked in ``benchmarks/speed_oneshot.py``.

Two implementations (DESIGN.md §9): the host reference ``_rollout`` (a
Python loop re-running a jitted full-sequence forward and a full cost-model
evaluation per step — the readable oracle) and the device-resident
``dnnfuser_infer_fused`` — one jitted ``jax.lax.scan`` fusing cached
single-token decode, the O(1) ``prefix_step`` env transition and a
``lax.while_loop`` halve-or-sync budget guard, zero host syncs inside the
episode.  Both roll any model implementing the ``backend.MapperBackend``
protocol (DESIGN §12): DT (KV cache) and seq2seq (streaming LSTM state)
ride the exact same episode code via ``backend_for``.

``dnnfuser_infer_batch`` vmaps the episode over a stacked batch of serving
conditions in one device call.  Since DESIGN §11 the accelerator is a
traced per-row condition (``accel.HwVec``); since §12 the WORKLOAD is too
(``cost_model.stack_workloads``: heterogeneous networks padded to a shared
``nmax``, positions past each row's true ``n`` masked to SYNC), so one
device call serves "resnet50 on mobile at 20 MB" next to "mnasnet on edge
at 8 MB".  This is the serving primitive ``repro.serving.MapperEngine``
and the benchmarks fan out over.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from .env import (FusionEnv, STATE_DIM, decode_action, encode_action,
                  decode_action_jnp, encode_action_jnp, env_make,
                  env_observe, env_reset, env_step, env_final)
from .backend import backend_for
from .accel import accel_features, as_hw, stack_hw
from . import cost_model as cm

__all__ = ["InferResult", "dnnfuser_infer", "s2s_infer",
           "dnnfuser_infer_fused", "s2s_infer_fused", "dnnfuser_infer_batch"]


@dataclass
class InferResult:
    strategy: np.ndarray
    speedup: float
    latency: float
    peak_mem: float
    valid: bool
    wall_s: float
    n_model_calls: int
    guard_iters: int         # the budget guard's halvings and syncs
    guard_syncs: int         # steps whose micro-batch the guard made SYNC


@partial(jax.jit, static_argnames=("cfg", "backend"))
def _forward(params, cfg, backend, rtg, states, actions, hw=None):
    return backend.forward(params, cfg, rtg, states, actions, hw)


def _hw_condition(cfg, env: FusionEnv):
    """The model's hw-condition row [1, F] (None for pre-§11 configs).

    Computed on the host from the SAME ``accel_features`` the batched
    front-end uses, so host and fused rollouts see bit-identical inputs."""
    if not getattr(cfg, "hw_dim", 0):
        return None
    return env.hw_features[None]


def _rollout(backend, params, cfg, env: FusionEnv, *,
             repair: bool) -> InferResult:
    T = cfg.max_steps
    rtg = np.zeros((1, T), np.float32)
    states = np.zeros((1, T, STATE_DIM), np.float32)
    actions = np.zeros((1, T), np.float32)
    hwf = _hw_condition(cfg, env)
    t0 = time.perf_counter()
    s = env.reset()
    calls = guard_iters = guard_syncs = 0
    for t in range(env.n + 1):
        states[0, t] = s
        rtg[0, t] = env.reward_to_go
        pred = _forward(params, cfg, backend, jnp.asarray(rtg),
                        jnp.asarray(states), jnp.asarray(actions),
                        None if hwf is None else jnp.asarray(hwf))
        calls += 1
        a_enc = float(pred[0, t])
        a = int(decode_action(a_enc, env.batch))
        if t == 0 and a < 1:
            a = 1                      # input micro-batch cannot sync
        if repair and a >= 1 and t > 0:
            # inference-time constraint guard (the model conditions on the
            # budget, but a hard guard keeps generalization runs valid):
            # shrink/sync if the staged buffer would overflow.
            while a >= 1:
                probe = env.actions.copy(); probe[t] = a
                pos = np.arange(env.nmax)
                probe = np.where(pos <= t, probe, cm.SYNC)
                out = env.evaluate_strategy(probe)
                if float(out.peak_mem) <= env.budget_bytes:
                    break
                a = a // 2 if a > 1 else cm.SYNC
                guard_iters += 1
            guard_syncs += int(a == cm.SYNC)
        actions[0, t] = encode_action(np.float32(a), env.batch)
        s, _, done = env.step(a)
    wall = time.perf_counter() - t0
    strat = env.actions.copy()
    out = env.evaluate_strategy(strat)
    return InferResult(strat, env.baseline_latency / float(out.latency),
                       float(out.latency), float(out.peak_mem),
                       bool(out.valid), wall, calls, guard_iters,
                       guard_syncs)


def dnnfuser_infer(params, cfg, env: FusionEnv, *,
                   repair: bool = True) -> InferResult:
    """Conditional autoregressive inference (host reference); works for any
    registered ``MapperBackend`` config (DT, seq2seq, ...)."""
    return _rollout(backend_for(cfg), params, cfg, env, repair=repair)


# ---------------------------------------------------------------------------
# Device-resident fused rollout (DESIGN.md §9, §12).
# ---------------------------------------------------------------------------


def _fused_episode(params, cfg, wl, batch, budget_bytes, hw,
                   hw_feats, repair: bool, backend) -> dict:
    """One (workload, batch, budget, accel) episode, fully traced.

    All control flow the host loop does in Python — the per-step env
    observation, the model call, the halve-or-sync budget guard and the env
    transition — runs inside one ``lax.scan`` (guard: ``lax.while_loop``),
    so the episode lowers to a single device program with no host syncs.
    Everything that varies per serving lane is traced data and vmaps:
    ``hw``/``hw_feats`` (DESIGN §11) and, since §12, the packed workload
    ``wl`` itself — positions past a lane's true ``n`` are masked to SYNC
    (``active``), which is what makes heterogeneous-length rows under one
    ``nmax`` bit-exact with their unpadded single-row rollouts.
    """
    consts = env_make(wl, batch, budget_bytes, hw)
    B, budget, n = consts.B, consts.budget, consts.n
    P = wl["A"].shape[0]
    hwb = None if hw_feats is None else hw_feats[None]

    def guard(carry, a):
        """The host probe loop: shrink / sync until the staged prefix plus
        an all-SYNC suffix fits the budget (paper's inference-time
        constraint guard).  Probes via the peak-only fast path.  Returns
        the action and how many times it was halved or synced."""
        def cond(c):
            return (c[0] >= 1) & (cm.prefix_probe_peak(consts.pc, carry, c[0],
                                                       hw) > budget)
        def body(c):
            av, k = c
            return jnp.where(av > 1, av // 2, jnp.int32(cm.SYNC)), k + 1
        return jax.lax.while_loop(cond, body, (a, jnp.int32(0)))

    # --- t = 0: prefill (r_0, s_0); the input micro-batch cannot sync ------
    with jax.named_scope("env_step"):
        carry0 = env_reset(consts)
        r0, s0 = env_observe(consts, carry0, hw)
    with jax.named_scope("dt_decode"):
        pred0, mstate = backend.prefill(params, cfg, backend.state_init(cfg),
                                        r0[None], s0[None], hwb)
        a0 = jnp.maximum(decode_action_jnp(pred0[0], B), 1)
    with jax.named_scope("env_step"):
        carry = env_step(consts, carry0, a0, hw)
        actions = jnp.full((P,), cm.SYNC, jnp.int32).at[0].set(a0)

    def step(sc, t):
        carry, mstate, a_prev, actions, iters, syncs = sc
        active = t <= n
        with jax.named_scope("env_step"):
            r_t, s_t = env_observe(consts, carry, hw)
        with jax.named_scope("dt_decode"):
            pred, mstate = backend.step(params, cfg, mstate, r_t[None],
                                        s_t[None],
                                        encode_action_jnp(a_prev, B)[None],
                                        hwb)
            a = decode_action_jnp(pred[0], B)
        if repair:
            with jax.named_scope("guard"):
                proposed = a
                a, k = guard(carry, a)
                iters = iters + jnp.where(active, k, 0)
                syncs = syncs + (active & (proposed >= 1)
                                 & (a == cm.SYNC)).astype(jnp.int32)
        with jax.named_scope("env_step"):
            a = jnp.where(active, a, jnp.int32(cm.SYNC))
            new_carry = env_step(consts, carry, a, hw)
            carry = cm._tree_select(active, new_carry, carry)
            actions = actions.at[t].set(a)
            a_prev = jnp.where(active, a, a_prev)
        return (carry, mstate, a_prev, actions, iters, syncs), None

    (carry, _, _, actions, iters, syncs), _ = jax.lax.scan(
        step, (carry, mstate, a0, actions, jnp.int32(0), jnp.int32(0)),
        jnp.arange(1, P))
    out = env_final(consts, carry, hw)
    return dict(strategy=actions, latency=out.latency,
                peak_mem=out.peak_mem, valid=out.valid,
                speedup=consts.base_lat / jnp.maximum(out.latency, 1e-12),
                baseline_latency=consts.base_lat, guard_iters=iters,
                guard_syncs=syncs)


@partial(jax.jit, static_argnames=("cfg", "repair", "backend"))
def _fused_one(params, cfg, wl, batch, budget_bytes, hw, hw_feats,
               repair, backend):
    return _fused_episode(params, cfg, wl, batch, budget_bytes, hw,
                          hw_feats, repair, backend)


@partial(jax.jit, static_argnames=("cfg", "repair", "backend", "stacked"))
def _fused_batch(params, cfg, wl, batches, budgets, hw, hw_feats,
                 repair, backend, stacked):
    # ``stacked`` workloads carry a leading per-row axis and vmap alongside
    # the other conditions; a shared workload broadcasts (in_axes None).
    return jax.vmap(
        lambda w, b, m, h, hf: _fused_episode(params, cfg, w, b, m, h, hf,
                                              repair, backend),
        in_axes=(0 if stacked else None, 0, 0, 0,
                 None if hw_feats is None else 0),
    )(wl, batches, budgets, hw, hw_feats)


def _fused_infer(backend, params, cfg, env: FusionEnv, repair) -> InferResult:
    hwf = _hw_condition(cfg, env)
    t0 = time.perf_counter()
    out = _fused_one(params, cfg, env.wl, float(env.batch),
                     float(env.budget_bytes), as_hw(env.hw),
                     None if hwf is None else jnp.asarray(hwf[0]),
                     repair, backend)
    strat = np.asarray(out["strategy"])          # device sync = episode end
    wall = time.perf_counter() - t0
    return InferResult(strat, float(out["speedup"]), float(out["latency"]),
                       float(out["peak_mem"]), bool(out["valid"]), wall,
                       env.n + 1, int(out["guard_iters"]),
                       int(out["guard_syncs"]))


def dnnfuser_infer_fused(params, cfg, env: FusionEnv, *,
                         repair: bool = True) -> InferResult:
    """Device-resident one-shot inference: emits the same strategy as
    :func:`dnnfuser_infer` from a single jitted scan."""
    return _fused_infer(backend_for(cfg), params, cfg, env, repair)


# Backend dispatch made the s2s entry points pure aliases (the config type
# selects seq2seq.S2SBackend); kept for API compatibility.
s2s_infer = dnnfuser_infer
s2s_infer_fused = dnnfuser_infer_fused


def dnnfuser_infer_batch(params, cfg, env_or_wl, batches,
                         budgets_bytes, hw=None, *,
                         repair: bool = True) -> dict:
    """Serve a stacked batch of (workload, batch, budget, accel) serving
    conditions in ONE device call.

    ``env_or_wl`` supplies the per-row workloads:
     - a FusionEnv (condition fields ignored) or a packed workload dict
       from ``cost_model.pack_workload`` — ONE network shared by all rows;
     - a sequence of FusionEnvs / packed dicts (same ``nmax``), or an
       already-stacked dict from ``cost_model.stack_workloads`` — a
       HETEROGENEOUS network per row, padded to the shared ``nmax`` with
       each row's positions past its true ``n`` masked to SYNC in the scan
       (DESIGN §12), bit-exact per row with the single-workload rollout.

    ``batches`` and ``budgets_bytes`` are same-length 1-D arrays.  ``hw``
    is optional with FusionEnvs (defaults to each env's accelerator) and
    accepts anything ``accel.stack_hw`` does — one ``AccelConfig``, a
    length-C sequence, a stacked ``HwVec``, or a raw ``[C, 10]`` array —
    heterogeneous per-row accelerators serve in the same fused call
    (DESIGN §11).  Any registered ``MapperBackend`` config works (DT and
    seq2seq).  Returns a dict of stacked arrays (strategy [C, P] int32,
    latency/peak_mem/speedup/valid [C], guard_iters [C]: the budget
    guard's halvings and syncs over each row's true steps, and guard_syncs
    [C]: the steps among them whose proposed micro-batch the guard ended
    at SYNC)."""
    if isinstance(env_or_wl, FusionEnv):
        wl = env_or_wl.wl
        if hw is None:
            hw = env_or_wl.hw
    elif isinstance(env_or_wl, (list, tuple)):
        rows = [e.wl if isinstance(e, FusionEnv) else e for e in env_or_wl]
        wl = cm.stack_workloads(rows)
        if hw is None:
            if not all(isinstance(e, FusionEnv) for e in env_or_wl):
                raise ValueError("hw is required with packed workloads")
            hw = [e.hw for e in env_or_wl]
    else:
        wl = env_or_wl
        if hw is None:
            raise ValueError("hw is required with a packed workload")
    batches = jnp.asarray(batches, jnp.float32)
    budgets = jnp.asarray(budgets_bytes, jnp.float32)
    C = batches.shape[0]
    stacked = jnp.ndim(wl["n"]) == 1
    if stacked and wl["n"].shape[0] != C:
        raise ValueError(f"stacked workloads have {wl['n'].shape[0]} rows, "
                         f"expected {C}")
    hwv = stack_hw(hw, C)
    # the model's condition rows are computed OUTSIDE the jit by the same
    # accel_features the host reference uses -> bit-identical inputs
    hwf = (jnp.asarray(np.asarray(accel_features(hwv), np.float32))
           if getattr(cfg, "hw_dim", 0) else None)
    out = _fused_batch(params, cfg, wl, batches, budgets, hwv, hwf,
                       repair, backend_for(cfg), stacked)
    return {k: np.asarray(v) for k, v in out.items()}
