"""The layered serving engine (DESIGN.md §12).

Pins the three §12 contracts:

 - **cross-workload batching**: a mixed [resnet18, mobilenet_v2, tiny_cnn]
   request batch through ``dnnfuser_infer_batch`` — heterogeneous true
   layer counts under one padded ``nmax`` — is per-row bit-exact with each
   workload served alone on BOTH the fused and the host reference paths;
 - **bucketing**: engine results (pow2-padded request batches, nmax-bucket
   padding, masked positions) are bit-exact with unbucketed single calls,
   and after warmup, traffic across all bucket shapes triggers ZERO new
   compilations (the recompile-churn guard);
 - **backend protocol**: DT and seq2seq ride the same rollout/serving code
   via ``backend_for``; the strategy LRU counts hits/misses and evicts.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import (ACCEL_ZOO, DTConfig, DTBackend, FusionEnv,
                        MapperEngine, MapRequest, PAPER_ACCEL, S2SConfig,
                        S2SBackend, StrategyCache, backend_for,
                        dnnfuser_infer, dnnfuser_infer_batch,
                        dnnfuser_infer_fused, dt_init, s2s_init)
from repro.core import cost_model as cm
from repro.core import infer as infer_mod
from repro.serving import (batch_bucket, budget_bucket,
                           default_nmax_buckets, nmax_bucket, pow2_buckets)
from repro.workloads import mobilenet_v2, resnet18, tiny_cnn, vgg16

MB = 2 ** 20


# --- bucketing primitives ---------------------------------------------------

def test_bucketing_primitives():
    assert [batch_bucket(c) for c in (1, 2, 3, 5, 8, 9)] == [1, 2, 4, 8, 8, 16]
    assert pow2_buckets(8) == (1, 2, 4, 8)
    assert default_nmax_buckets(20) == (8, 16, 20)
    assert default_nmax_buckets(64) == (8, 16, 32, 64)
    assert nmax_bucket(7, (8, 16, 20)) == 8
    assert nmax_bucket(17, (8, 16, 20)) == 20
    with pytest.raises(ValueError):
        nmax_bucket(21, (8, 16, 20))
    assert budget_bucket(20 * MB) == budget_bucket(20 * MB + 1000)
    assert budget_bucket(20 * MB) != budget_bucket(21 * MB)


def test_strategy_cache_lru_counters_and_eviction():
    c = StrategyCache(capacity=2)
    assert c.get("a") is None and c.misses == 1
    c.put("a", 1); c.put("b", 2)
    assert c.get("a") == 1 and c.hits == 1
    c.put("c", 3)                      # evicts "b" (least recent)
    assert "b" not in c and "a" in c and len(c) == 2
    assert c.get("b") is None
    assert 0.0 < c.hit_rate < 1.0


def test_stack_workloads_rejects_mixed_nmax():
    with pytest.raises(ValueError, match="different nmax"):
        cm.stack_workloads([cm.pack_workload(tiny_cnn(), PAPER_ACCEL, 8),
                            cm.pack_workload(tiny_cnn(), PAPER_ACCEL, 16)])


# --- cross-workload batching (the §12 core contract) ------------------------

def test_mixed_network_batch_matches_each_served_alone():
    """[resnet18, mobilenet_v2, tiny_cnn] — three true layer counts (18,
    53, 6) under one nmax=64 — served in ONE device call must be per-row
    bit-exact with every workload served alone, on the fused AND the host
    reference paths."""
    cfg = DTConfig(max_steps=64)
    params = dt_init(jax.random.PRNGKey(0), cfg)
    conds = [(resnet18(), ACCEL_ZOO["edge"], 64, 20 * MB),
             (mobilenet_v2(), ACCEL_ZOO["mobile"], 32, 12 * MB),
             (tiny_cnn(), ACCEL_ZOO["edge"], 16, 2 * MB)]
    envs = [FusionEnv(w, acc, batch=b, budget_bytes=m, nmax=64)
            for w, acc, b, m in conds]
    out = dnnfuser_infer_batch(params, cfg, envs,
                               np.asarray([c[2] for c in conds], np.float32),
                               np.asarray([c[3] for c in conds], np.float32))
    assert out["strategy"].shape == (3, 64)
    for i, env in enumerate(envs):
        fused = dnnfuser_infer_fused(params, cfg, env)
        host = dnnfuser_infer(params, cfg, env)
        assert (out["strategy"][i] == fused.strategy).all(), i
        assert (out["strategy"][i] == host.strategy).all(), i
        np.testing.assert_allclose(out["latency"][i], fused.latency,
                                   rtol=1e-5)
        assert bool(out["valid"][i]) == fused.valid
        # padding positions past the true n stay SYNC
        assert (out["strategy"][i][env.n + 1:] == cm.SYNC).all()


def test_stacked_workload_dict_and_hw_validation():
    cfg = DTConfig(max_steps=20)
    params = dt_init(jax.random.PRNGKey(1), cfg)
    wls = [cm.pack_workload(vgg16(), PAPER_ACCEL, 20),
           cm.pack_workload(tiny_cnn(), PAPER_ACCEL, 20)]
    stacked = cm.stack_workloads(wls)
    with pytest.raises(ValueError, match="hw is required"):
        dnnfuser_infer_batch(params, cfg, stacked, [64.0, 64.0],
                             [20 * MB, 20 * MB])
    with pytest.raises(ValueError, match="rows"):
        dnnfuser_infer_batch(params, cfg, stacked, [64.0], [20 * MB],
                             PAPER_ACCEL)
    out = dnnfuser_infer_batch(params, cfg, stacked, [64.0, 64.0],
                               [20 * MB, 20 * MB], PAPER_ACCEL)
    for i, w in enumerate((vgg16(), tiny_cnn())):
        env = FusionEnv(w, PAPER_ACCEL, batch=64, budget_bytes=20 * MB,
                        nmax=20)
        one = dnnfuser_infer_fused(params, cfg, env)
        assert (out["strategy"][i] == one.strategy).all(), i


# --- the engine -------------------------------------------------------------

CFG = DTConfig(max_steps=20)
PARAMS = dt_init(jax.random.PRNGKey(2), CFG)


def _mixed_requests(rng, n):
    nets = [vgg16(), resnet18(), tiny_cnn()]
    accs = [ACCEL_ZOO["edge"], ACCEL_ZOO["mobile"], ACCEL_ZOO["laptop"]]
    return [MapRequest(nets[rng.integers(len(nets))],
                       int(rng.choice([16, 32, 64])),
                       float(rng.integers(6, 48)) * MB,
                       accs[rng.integers(len(accs))]) for _ in range(n)]


def test_engine_bucketed_results_bit_exact_with_unbucketed():
    """A 3-request group pads to a 4-lane bucket; every real row must equal
    its own unbucketed fused rollout (and the padded lanes must not leak
    into the responses)."""
    eng = MapperEngine(PARAMS, CFG)
    reqs = [MapRequest(vgg16(), 64, 20 * MB, ACCEL_ZOO["edge"]),
            MapRequest(resnet18(), 32, 14 * MB, ACCEL_ZOO["mobile"]),
            MapRequest(vgg16(), 16, 9 * MB, ACCEL_ZOO["laptop"]),
            MapRequest(tiny_cnn(), 64, 3 * MB, ACCEL_ZOO["edge"])]
    out = eng.serve(reqs)                        # groups: nmax20 x3, nmax8 x1
    assert eng.rows_padded == 1                  # 3 -> pow2 bucket of 4
    for req, resp in zip(reqs, out):
        env = FusionEnv(req.workload, req.accel, batch=req.batch,
                        budget_bytes=req.budget_bytes,
                        nmax=nmax_bucket(req.workload.n + 1,
                                         eng.nmax_buckets))
        one = dnnfuser_infer_fused(PARAMS, CFG, env)
        assert resp.strategy.shape == (req.workload.n + 1,)
        assert (resp.strategy == one.strategy[: req.workload.n + 1]).all()
        np.testing.assert_allclose(resp.latency, one.latency, rtol=1e-5)
        assert resp.valid == one.valid


def test_engine_zero_recompiles_after_warmup():
    """The churn guard: warmup covers the (nmax x pow2-batch) bucket grid;
    serving mixed traffic across ALL those shapes afterwards must not
    materialize a single new program."""
    eng = MapperEngine(PARAMS, CFG)
    compiled = eng.warmup([vgg16(), resnet18(), tiny_cnn()],
                          ACCEL_ZOO["edge"], max_tick=8)
    assert compiled == eng.compile_count > 0
    jit_cache = getattr(infer_mod._fused_batch, "_cache_size", None)
    jit_before = jit_cache() if jit_cache else None
    before = eng.compile_count
    rng = np.random.default_rng(1)
    for tick in (1, 2, 3, 5, 7, 8):              # every bucket shape
        eng.serve(_mixed_requests(rng, tick))
    assert eng.compile_count == before, "recompile churn in steady state"
    if jit_cache is not None:                    # cross-check jax's cache
        assert jit_cache() == jit_before, \
            "engine counter says 0 but jax compiled new programs"
    assert eng.stats()["strategy_misses"] > 0    # it did real device work


def test_engine_exact_budget_identity_is_default():
    """DESIGN §14: by default the strategy identity is the EXACT condition
    — a nearby (same-quantum) budget is a different condition and must
    NOT reuse the cached strategy, which is what makes coalesced serving
    bit-identical to per-request serving regardless of arrival order."""
    eng = MapperEngine(PARAMS, CFG)
    req = MapRequest(vgg16(), 64, 20 * MB, ACCEL_ZOO["edge"])
    r1 = eng.serve_one(req)
    assert not r1.cached
    r2 = eng.serve_one(req)                      # identical condition: hit
    assert r2.cached and (r2.strategy == r1.strategy).all()
    near = eng.serve_one(MapRequest(vgg16(), 64, 20 * MB + 1000,
                                    ACCEL_ZOO["edge"]))
    assert not near.cached                       # nearby budget: solved fresh
    # in-tick dedup follows the same identity: only EXACT duplicates share
    # a lane
    eng2 = MapperEngine(PARAMS, CFG)
    eng2.serve([req, MapRequest(vgg16(), 64, 20 * MB + 1000,
                                ACCEL_ZOO["edge"])])
    assert eng2.tick_dedup == 0
    eng2.serve([MapRequest(resnet18(), 32, 14 * MB, ACCEL_ZOO["mobile"]),
                MapRequest(resnet18(), 32, 14 * MB, ACCEL_ZOO["mobile"])])
    assert eng2.tick_dedup == 1


def test_engine_strategy_cache_hits_and_budget_quantization():
    """The opt-in ``approx_budget_sharing=True`` mode restores quantized
    budget identities (same-quantum conditions share one solved strategy)
    while validity stays per-request."""
    eng = MapperEngine(PARAMS, CFG, budget_quantum=MB,
                       approx_budget_sharing=True)
    req = MapRequest(vgg16(), 64, 20 * MB, ACCEL_ZOO["edge"])
    r1 = eng.serve_one(req)
    assert not r1.cached
    # same condition -> hit; nearby budget in the same 1 MB quantum -> hit
    r2 = eng.serve_one(req)
    r3 = eng.serve_one(MapRequest(vgg16(), 64, 20 * MB + 1000,
                                  ACCEL_ZOO["edge"]))
    assert r2.cached and r3.cached
    assert (r2.strategy == r1.strategy).all()
    # validity is re-derived against the EXACT requested budget: a reused
    # strategy must never be called valid for a budget it overflows
    tight = eng.serve_one(MapRequest(vgg16(), 64,
                                     max(r1.peak_mem - 1.0, 1.0),
                                     ACCEL_ZOO["edge"]))
    if tight.cached:
        assert not tight.valid
    # in-tick duplicates share one device lane but keep PER-REQUEST
    # validity: a huge budget_quantum collapses a generous and an
    # impossible budget into one bucket — the impossible one must still
    # come back invalid
    wide = MapperEngine(PARAMS, CFG, budget_quantum=64 * MB,
                        approx_budget_sharing=True)
    roomy, tiny = wide.serve([
        MapRequest(vgg16(), 64, 40 * MB, ACCEL_ZOO["edge"]),
        MapRequest(vgg16(), 64, 1024.0, ACCEL_ZOO["edge"])])
    assert wide.tick_dedup == 1 and tiny.cached
    assert roomy.valid and not tiny.valid
    # different batch / budget bucket / accel are distinct conditions
    assert not eng.serve_one(MapRequest(vgg16(), 32, 20 * MB,
                                        ACCEL_ZOO["edge"])).cached
    assert not eng.serve_one(MapRequest(vgg16(), 64, 26 * MB,
                                        ACCEL_ZOO["edge"])).cached
    assert not eng.serve_one(MapRequest(vgg16(), 64, 20 * MB,
                                        ACCEL_ZOO["mobile"])).cached
    assert eng.stats()["strategy_hit_rate"] > 0


def test_engine_rejects_oversized_bucket_config():
    with pytest.raises(ValueError, match="max_steps"):
        MapperEngine(PARAMS, CFG, nmax_buckets=(8, 64))
    eng = MapperEngine(PARAMS, CFG)              # mobilenet (n=53) > 20
    with pytest.raises(ValueError, match="nmax bucket"):
        eng.serve_one(MapRequest(mobilenet_v2(), 64, 20 * MB, PAPER_ACCEL))


# --- persistent strategy cache (DESIGN §14) ---------------------------------

def test_strategy_cache_persists_across_engines(tmp_path):
    """Cross-process amortization: strategies solved by one engine, saved,
    then loaded read-through by a FRESH engine must serve as hits — no
    device calls, no compiles — and bit-identically."""
    path = tmp_path / "strategies.json"
    eng = MapperEngine(PARAMS, CFG)
    reqs = [MapRequest(vgg16(), 64, 20 * MB, ACCEL_ZOO["edge"]),
            MapRequest(tiny_cnn(), 16, 3 * MB, ACCEL_ZOO["mobile"])]
    first = eng.serve(reqs)
    assert eng.save_cache(path) == len(reqs)
    fresh = MapperEngine(PARAMS, CFG, cache_path=path)
    again = fresh.serve(reqs)
    assert fresh.device_calls == 0 and fresh.compile_count == 0
    for a, b in zip(first, again):
        assert b.cached and (a.strategy == b.strategy).all()
        assert a.latency == b.latency and a.valid == b.valid
    assert fresh.strategies.shared_hits == len(reqs)
    # merge-write: a second engine's strategies union into the same file
    eng2 = MapperEngine(PARAMS, CFG)
    extra = MapRequest(resnet18(), 32, 14 * MB, ACCEL_ZOO["laptop"])
    eng2.serve_one(extra)
    assert eng2.save_cache(path) == 1 + len(reqs)
    both = MapperEngine(PARAMS, CFG, cache_path=path)
    assert both.serve_cached(extra) is not None
    assert both.serve_cached(reqs[0]) is not None


def test_strategy_cache_rejects_stale_checkpoint(tmp_path):
    """A persisted cache is keyed to its checkpoint fingerprint: a file
    written under different params must load ZERO entries (and raise
    under strict=True) — never serve another checkpoint's strategies."""
    path = tmp_path / "strategies.json"
    eng = MapperEngine(PARAMS, CFG)
    eng.serve_one(MapRequest(vgg16(), 64, 20 * MB, ACCEL_ZOO["edge"]))
    eng.save_cache(path)
    other_params = dt_init(jax.random.PRNGKey(7), CFG)
    other = MapperEngine(other_params, CFG)
    assert other.load_cache(path) == 0
    assert other.strategies.stale_skipped == 1
    with pytest.raises(ValueError, match="incompatible"):
        other.load_cache(path, strict=True)
    # budget-identity modes don't share files either: exact keys must not
    # resolve against quantized ones
    approx = MapperEngine(PARAMS, CFG, approx_budget_sharing=True)
    assert approx.load_cache(path) == 0


def test_engine_stats_schema():
    """S2: one observability dict across every layer — queueing, admission,
    coalescing, per-replica and cache persistence counters all in one
    ``stats()`` call."""
    from repro.serving import AsyncMapperScheduler
    eng = MapperEngine(PARAMS, CFG)
    sched = AsyncMapperScheduler(eng, flush_ms=0.0, max_wave=4)
    sched.submit(MapRequest(vgg16(), 64, 20 * MB, ACCEL_ZOO["edge"]), now=0.0)
    sched.drain(0.01)
    s = eng.stats()
    for key in ("requests_served", "device_calls", "compile_count",
                "compiled_shapes", "chunk_cap", "rows_padded", "tick_dedup",
                "coalesce_width_hist", "strategy_hit_rate", "strategy_cache",
                "replicas", "scheduler", "drift",
                "escalations", "polish_invocations", "polish_improved",
                "ticks", "guard_iters", "guard_syncs", "rollout_steps",
                "spans"):
        assert key in s, key
    # host spans: one tick, one device call in four phases
    spans = s["spans"]
    for name in ("engine.serve", "engine.pack", "engine.dispatch",
                 "engine.wait", "engine.unpack"):
        assert spans[name]["seconds"] >= 0.0, name
    assert spans["engine.wait"]["count"] == s["device_calls"] == 1
    assert spans["engine.serve"]["count"] == s["ticks"] == 1
    assert "engine.warmup" not in spans            # never warmed
    assert s["rollout_steps"] == vgg16().n + 1
    assert s["guard_iters"] >= s["guard_syncs"] >= 0
    # §17 refinement is off by default: counters exist but never move
    assert (s["escalations"], s["polish_invocations"],
            s["polish_improved"]) == (0, 0, 0)
    assert s["coalesce_width_hist"] == {1: 1}
    for key in ("entries", "capacity", "shared_hits", "loads", "saves",
                "stale_skipped"):
        assert key in s["strategy_cache"], key
    for key in ("queue_depth", "max_queue_depth", "submitted", "rejected",
                "resolved_at_submit", "flushes", "queue_wait_s", "queued",
                "spans"):
        assert key in s["scheduler"], key
    assert s["scheduler"]["submitted"] == 1
    assert s["scheduler"]["queued"] == 1
    assert s["scheduler"]["spans"]["scheduler.pump"]["count"] == 1
    assert s["scheduler"]["spans"]["scheduler.flush"]["count"] == 1
    assert s["replicas"] is None                 # unreplicated engine
    # §15 closed-loop counters: replay/telemetry, drift windows, swaps
    for key in ("replay_depth", "replay_capacity", "replay_total",
                "windows_evaluated", "reports_fired", "pending_reports",
                "swaps_accepted", "swaps_rejected", "cache_invalidated",
                "last_report"):
        assert key in s["drift"], key
    assert s["drift"]["replay_depth"] == 1       # the one served request
    assert s["drift"]["swaps_accepted"] == 0
    # the span tallies keep counting; warmup gets its own span
    calls = s["device_calls"]
    eng.warmup([tiny_cnn()], ACCEL_ZOO["edge"], max_tick=2)
    s = eng.stats()
    assert s["spans"]["engine.warmup"]["count"] == 1
    assert s["spans"]["engine.wait"]["count"] == s["device_calls"] == calls + 2


# --- backend protocol -------------------------------------------------------

def test_backend_registry_resolves_and_rejects():
    assert backend_for(DTConfig()) is DTBackend
    assert backend_for(S2SConfig()) is S2SBackend
    with pytest.raises(TypeError, match="no MapperBackend"):
        backend_for(object())


def test_s2s_rides_the_same_batched_serving_path():
    """The seq2seq baseline serves through the SAME fused/batched rollout
    (and the engine) via backend dispatch — no model-specific plumbing."""
    cfg = S2SConfig(max_steps=20)
    params = s2s_init(jax.random.PRNGKey(3), cfg)
    env = FusionEnv(vgg16(), PAPER_ACCEL, batch=64, budget_bytes=16 * MB,
                    nmax=20)
    one = dnnfuser_infer_fused(params, cfg, env)
    out = dnnfuser_infer_batch(params, cfg, env, [64.0], [16 * MB])
    assert (out["strategy"][0] == one.strategy).all()
    eng = MapperEngine(params, cfg)
    resp = eng.serve_one(MapRequest(vgg16(), 64, 16 * MB, PAPER_ACCEL))
    assert (resp.strategy == one.strategy[: vgg16().n + 1]).all()
