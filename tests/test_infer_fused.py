"""Device-resident one-shot inference: the fused scan rollout must be an
exact stand-in for the host reference path (DESIGN.md §9).

 - ``prefix_step`` carry matches ``prefix_trace`` at every t;
 - ``prefix_probe_peak`` equals the composed step+out probe;
 - ``dt_decode_step`` with a KV cache matches full-sequence ``dt_apply``;
 - ``s2s_decode_step`` replays teacher-forced ``s2s_apply`` exactly;
 - the fused rollout emits strategies bit-identical to the host loop
   (guard off and on), and the batched front-end matches per-condition runs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (DTConfig, FusionEnv, PAPER_ACCEL, S2SConfig,
                        dnnfuser_infer, dnnfuser_infer_batch,
                        dnnfuser_infer_fused, dt_apply, dt_cache_init,
                        dt_decode_step, dt_init, dt_prefill, s2s_apply,
                        s2s_decode_start, s2s_decode_step, s2s_encode,
                        s2s_infer_fused, s2s_init)
from repro.core import cost_model as cm
from repro.workloads import mobilenet_v2, resnet18, vgg16

HW = PAPER_ACCEL
MB = 2 ** 20
CFG = DTConfig(max_steps=20)


# --- incremental prefix evaluator ------------------------------------------

@pytest.mark.parametrize("wl_fn", [vgg16, resnet18, mobilenet_v2])
def test_prefix_scan_matches_prefix_trace(wl_fn):
    w = wl_fn()
    wl = cm.pack_workload(w, HW, 64)
    rng = np.random.default_rng(0)
    for _ in range(8):
        s = cm.random_strategy(rng, w.n, 64, 64, p_sync=0.35)
        tr = cm.prefix_trace(wl, jnp.asarray(s), 64.0, 20 * MB, HW)
        sc, fin = cm.prefix_scan(wl, jnp.asarray(s), 64.0, 20 * MB, HW)
        for k in ("latency", "peak_mem", "traffic"):
            np.testing.assert_allclose(
                np.asarray(getattr(sc, k)), np.asarray(getattr(tr, k)),
                rtol=1e-5, atol=1e-3, err_msg=k)
        assert (np.asarray(sc.n_groups) == np.asarray(tr.n_groups)).all()
        full = cm.evaluate(wl, jnp.asarray(s), 64.0, 20 * MB, HW)
        np.testing.assert_allclose(float(fin.latency), float(full.latency),
                                   rtol=1e-5)
        np.testing.assert_allclose(float(fin.peak_mem), float(full.peak_mem),
                                   rtol=1e-5)
        assert bool(fin.valid) == bool(full.valid)


def test_prefix_probe_peak_matches_composed_probe():
    w = resnet18()
    wl = cm.pack_workload(w, HW, 64)
    consts = cm.prefix_consts(wl, 64.0, 20 * MB, HW)
    carry = cm.prefix_init(consts)
    rng = np.random.default_rng(1)
    s = cm.random_strategy(rng, w.n, 64, 64)
    for t in range(w.n + 1):
        for a in (1, 5, 32, 64):
            ref = cm.prefix_out(
                consts, cm.prefix_step(consts, carry, a, HW), HW).peak_mem
            fast = cm.prefix_probe_peak(consts, carry, a, HW)
            assert float(ref) == float(fast), (t, a)
        carry = cm.prefix_step(consts, carry, int(s[t]), HW)


# --- cached decode vs full-sequence forward --------------------------------

def test_dt_decode_step_matches_dt_apply():
    params = dt_init(jax.random.PRNGKey(0), CFG)
    rng = np.random.default_rng(0)
    T = CFG.max_steps
    rtg = jnp.asarray(rng.random((1, T)), jnp.float32)
    states = jnp.asarray(rng.random((1, T, 8)), jnp.float32)
    actions = jnp.asarray(rng.random((1, T)), jnp.float32)
    full = np.asarray(dt_apply(params, CFG, rtg, states, actions))[0]
    cache = dt_cache_init(CFG)
    pred, cache = dt_prefill(params, CFG, cache, rtg[:, 0], states[:, 0])
    preds = [float(pred[0])]
    for t in range(1, T):
        pred, cache = dt_decode_step(params, CFG, cache, rtg[:, t],
                                     states[:, t], actions[:, t - 1])
        preds.append(float(pred[0]))
    np.testing.assert_allclose(np.array(preds), full, atol=1e-5)


def test_s2s_decode_step_matches_s2s_apply():
    cfg = S2SConfig(max_steps=20)
    params = s2s_init(jax.random.PRNGKey(1), cfg)
    rng = np.random.default_rng(1)
    T = cfg.max_steps
    rtg = jnp.asarray(rng.random((1, T)), jnp.float32)
    states = jnp.asarray(rng.random((1, T, 8)), jnp.float32)
    actions = jnp.asarray(rng.random((1, T)), jnp.float32)
    full = np.asarray(s2s_apply(params, cfg, rtg, states, actions))[0]
    cache = s2s_decode_start(s2s_encode(params, cfg, rtg, states))
    prev = jnp.zeros((1,), jnp.float32)
    preds = []
    for t in range(T):
        pred, cache = s2s_decode_step(params, cfg, cache, rtg[:, t],
                                      states[:, t], prev)
        preds.append(float(pred[0]))
        prev = actions[:, t]
    np.testing.assert_allclose(np.array(preds), full, atol=1e-5)


# --- fused rollout vs host reference ---------------------------------------

def _biased(params, bias):
    """Shift the action head so the model asks for large micro-batches
    (forces the budget-repair guard to engage)."""
    p = jax.tree_util.tree_map(lambda x: x, params)
    p["head"] = dict(params["head"])
    p["head"]["b"] = params["head"]["b"] + bias
    return p


@pytest.mark.parametrize("wl_fn", [vgg16, resnet18])
def test_fused_rollout_identical_to_host(wl_fn):
    wl = wl_fn()
    for seed in (0, 1):
        params = dt_init(jax.random.PRNGKey(seed), CFG)
        for budget_mb in (12, 20, 48):
            env = FusionEnv(wl, HW, batch=64, budget_bytes=budget_mb * MB,
                            nmax=CFG.max_steps)
            for repair in (False, True):
                h = dnnfuser_infer(params, CFG, env, repair=repair)
                f = dnnfuser_infer_fused(params, CFG, env, repair=repair)
                assert (h.strategy == f.strategy).all(), \
                    (seed, budget_mb, repair)
                np.testing.assert_allclose(f.latency, h.latency, rtol=1e-5)
                assert f.valid == h.valid
                assert f.n_model_calls == wl.n + 1


def test_fused_guard_repairs_over_budget_strategies():
    wl = vgg16()
    params = _biased(dt_init(jax.random.PRNGKey(0), CFG), 0.9)
    for budget_mb in (4, 6, 10):
        env = FusionEnv(wl, HW, batch=64, budget_bytes=budget_mb * MB,
                        nmax=CFG.max_steps)
        raw = dnnfuser_infer_fused(params, CFG, env, repair=False)
        assert not raw.valid        # the biased model overshoots ...
        h = dnnfuser_infer(params, CFG, env, repair=True)
        f = dnnfuser_infer_fused(params, CFG, env, repair=True)
        assert f.valid              # ... and the on-device guard repairs it
        assert f.peak_mem <= env.budget_bytes
        assert (h.strategy == f.strategy).all()


def test_guard_iters_count_the_host_guards_halvings_and_syncs():
    """``guard_iters`` counts, lane by lane, the halvings and syncs the
    host guard makes over the lane's true steps; the other outputs keep
    matching the host rollout."""
    wl = vgg16()
    params = _biased(dt_init(jax.random.PRNGKey(0), CFG), 0.9)
    batches = np.array([64.0, 32.0, 16.0, 64.0], np.float32)
    budgets = np.array([4.0, 6.0, 10.0, 64.0], np.float32) * MB
    env0 = FusionEnv(wl, HW, batch=64, budget_bytes=32 * MB,
                     nmax=CFG.max_steps)
    out = dnnfuser_infer_batch(params, CFG, env0, batches, budgets)
    off = dnnfuser_infer_batch(params, CFG, env0, batches, budgets,
                               repair=False)
    assert (off["guard_iters"] == 0).all()
    for i in range(len(batches)):
        env = FusionEnv(wl, HW, batch=int(batches[i]),
                        budget_bytes=float(budgets[i]), nmax=CFG.max_steps)
        h = dnnfuser_infer(params, CFG, env, repair=True)
        assert int(out["guard_iters"][i]) == h.guard_iters, i
        assert (out["strategy"][i] == h.strategy).all(), i
        np.testing.assert_allclose(out["latency"][i], h.latency, rtol=1e-5)
        f = dnnfuser_infer_fused(params, CFG, env, repair=True)
        assert f.guard_iters == h.guard_iters
    assert out["guard_iters"][:3].min() > 0     # the guard did work


def test_guard_syncs_count_the_host_guards_forced_syncs():
    """``guard_syncs`` counts, lane by lane, the steps whose proposed
    micro-batch the host guard ended at SYNC; each is also one of the
    ``guard_iters``."""
    wl = vgg16()
    params = _biased(dt_init(jax.random.PRNGKey(0), CFG), 0.9)
    batches = np.array([64.0, 32.0, 16.0, 64.0], np.float32)
    budgets = np.array([4.0, 6.0, 10.0, 64.0], np.float32) * MB
    env0 = FusionEnv(wl, HW, batch=64, budget_bytes=32 * MB,
                     nmax=CFG.max_steps)
    out = dnnfuser_infer_batch(params, CFG, env0, batches, budgets)
    off = dnnfuser_infer_batch(params, CFG, env0, batches, budgets,
                               repair=False)
    assert (off["guard_syncs"] == 0).all()
    assert (out["guard_syncs"] <= out["guard_iters"]).all()
    for i in range(len(batches)):
        env = FusionEnv(wl, HW, batch=int(batches[i]),
                        budget_bytes=float(budgets[i]), nmax=CFG.max_steps)
        h = dnnfuser_infer(params, CFG, env, repair=True)
        assert int(out["guard_syncs"][i]) == h.guard_syncs, i
        f = dnnfuser_infer_fused(params, CFG, env, repair=True)
        assert f.guard_syncs == h.guard_syncs
    assert out["guard_syncs"][0] > 0            # the 4 MB lane is forced


def test_infer_batch_matches_single_condition_runs():
    wl = resnet18()
    params = dt_init(jax.random.PRNGKey(2), CFG)
    batches = np.array([64.0, 64.0, 32.0, 16.0], np.float32)
    budgets = np.array([12.0, 32.0, 20.0, 20.0], np.float32) * MB
    env0 = FusionEnv(wl, HW, batch=64, budget_bytes=32 * MB,
                     nmax=CFG.max_steps)
    out = dnnfuser_infer_batch(params, CFG, env0, batches, budgets)
    assert out["strategy"].shape == (4, CFG.max_steps)
    for i in range(len(batches)):
        env = FusionEnv(wl, HW, batch=int(batches[i]),
                        budget_bytes=float(budgets[i]), nmax=CFG.max_steps)
        one = dnnfuser_infer_fused(params, CFG, env)
        assert (out["strategy"][i] == one.strategy).all(), i
        np.testing.assert_allclose(out["latency"][i], one.latency,
                                   rtol=1e-5)


def test_s2s_fused_rollout_valid():
    cfg = S2SConfig(max_steps=20)
    params = s2s_init(jax.random.PRNGKey(3), cfg)
    env = FusionEnv(resnet18(), HW, batch=64, budget_bytes=16 * MB,
                    nmax=cfg.max_steps)
    res = s2s_infer_fused(params, cfg, env, repair=True)
    assert res.valid and np.isfinite(res.latency)
    assert res.peak_mem <= env.budget_bytes
