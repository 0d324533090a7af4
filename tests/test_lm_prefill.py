"""DeepSeek-V3's prefill as a sublayer chain (``workloads.lm_workloads``)
and its 126 positions on the normal serving path.

 - the lowering equals a plain numpy lowering written here from the
   equations of each sublayer, at a small DeepSeek-shaped config;
 - the chip's expert share ties to the uncut model: over all shares the
   held experts' weights, with the router and the shared expert counted
   once, are the uncut layer's, and likewise the multiply-adds;
 - at the published widths the chain has the published numbers;
 - a 126-position chain is served through ``repro.serve`` at nmax 128 by a
   ``max_steps`` 128 mapper, bit for bit the host rollout, at the costs of
   the f64 oracle, with the host's count of guard-forced SYNCs.
"""
import dataclasses

import jax
import numpy as np
import pytest

import repro
from repro.configs import ARCH_NAMES, MAPPED_NAMES, get_config
from repro.core import (ACCEL_ZOO, DTConfig, FusionEnv, MapRequest,
                        dnnfuser_infer, dt_init)
from repro.core import cost_model as cm, ref_model
from repro.workloads import LM_PREFILL, get_workload
from repro.workloads.lm_workloads import lm_workload, mla_prefill_workload

MB = 2 ** 20

# d 64, 4 heads, 1 dense + 4 MoE layers, 16 experts top-4, 4 held, MTP on
SMALL = dataclasses.replace(
    get_config("deepseek_v3"), name="dsv3_small", n_layers=5, d_model=64,
    n_heads=4, kv_heads=4, d_ff=96, vocab=500, n_experts=16, moe_top_k=4,
    moe_d_ff=24, n_shared_experts=1, first_k_dense_replace=1,
    experts_held=4, q_lora_rank=32, kv_lora_rank=16, qk_nope_head_dim=12,
    qk_rope_head_dim=4, v_head_dim=8, mtp_layers=1)


def _plain_lowering(c, S: int, held: int) -> dict:
    """Per-position arrays of the chain, from the equations alone."""
    d, H = c.d_model, c.n_heads
    qk = c.qk_nope_head_dim + c.qk_rope_head_dim
    mla = (d * c.q_lora_rank + c.q_lora_rank * H * qk
           + d * (c.kv_lora_rank + c.qk_rope_head_dim)
           + c.kv_lora_rank * H * (c.qk_nope_head_dim + c.v_head_dim)
           + H * c.v_head_dim * d)
    norms = d + c.q_lora_rank + c.kv_lora_rank
    pairs = sum(i + 1 for i in range(S))           # causal (query, key)
    core = pairs * H * (qk + c.v_head_dim)
    e = 3 * d * c.moe_d_ff
    moe = (d * c.n_experts + c.n_shared_experts * e + held * e,
           S * (d * c.n_experts + c.n_shared_experts * e + c.moe_top_k * e))
    W, F, OE, SKIP = [0], [0], [S * d], [-1]
    for layer in range(c.n_layers):
        W.append(mla + norms); F.append(S * mla + core)
        dense = layer < c.first_k_dense_replace
        w, f = (3 * d * c.d_ff, S * 3 * d * c.d_ff) if dense else moe
        W.append(w); F.append(f)
        OE += [S * d, S * d]; SKIP += [-1, -1]
    W.append(2 * d * d + 2 * d + mla + norms)
    F.append(S * (2 * d * d + mla) + core)
    W.append(moe[0]); F.append(moe[1])
    OE += [S * d, S * d]; SKIP += [0, -1]
    W.append(c.vocab * d); F.append(2 * c.vocab * d)
    OE.append(2 * c.vocab); SKIP.append(2 * c.n_layers)
    return dict(W=np.array(W, float), F=np.array(F, float),
                OE=np.array(OE, float), SKIP=np.array(SKIP))


@pytest.mark.parametrize("S", [1, 24, 1000])
def test_mla_lowering_matches_a_plain_numpy_lowering(S):
    w = lm_workload(SMALL, seq_len=S, batch=8, mode="prefill")
    want = _plain_lowering(SMALL, S, held=4)
    n = len(want["W"]) - 1
    assert w.n == n == 2 * SMALL.n_layers + 3
    got = w.arrays(n + 1, bytes_per_elem=2.0)
    np.testing.assert_array_equal(got["W"], want["W"] * 2.0)
    np.testing.assert_array_equal(got["F"], want["F"])
    np.testing.assert_array_equal(got["A"], want["OE"] * 2.0)
    np.testing.assert_array_equal(got["OE"][1:], want["OE"][1:])
    np.testing.assert_array_equal(got["SKIP"], want["SKIP"])
    assert [l.name for l in w.layers[:4]] == ["attn0", "ffn0", "attn1",
                                               "moe1"]
    assert [l.name for l in w.layers[-3:]] == ["mtp.attn", "mtp.moe", "head"]


def test_expert_share_ties_to_the_uncut_model():
    """Four chips of EP4 hold 4 of the 16 experts each; under balanced
    routing each chip's experts receive the top-k pairs of its own
    tokens.  Over the four shares, the held experts' weights plus the
    router and shared expert counted once are the uncut layer's, and the
    shares' multiply-adds are the uncut layer's over all four chips'
    tokens.  Every other sublayer is the same on every chip."""
    S, c = 24, SMALL
    shares = c.n_experts // c.experts_held
    part = mla_prefill_workload(c, seq_len=S)
    whole = mla_prefill_workload(
        dataclasses.replace(c, experts_held=c.n_experts), seq_len=S)
    e = 3 * c.d_model * c.moe_d_ff
    once = c.d_model * c.n_experts + c.n_shared_experts * e
    for lp, lw in zip(part.layers, whole.layers):
        if not lp.name.startswith(("moe", "mtp.moe")):
            assert (lp.w_elems, lp.macs) == (lw.w_elems, lw.macs)
            continue
        assert shares * lp.w_elems - (shares - 1) * once == lw.w_elems
        assert lw.w_elems == once + c.n_experts * e
        # balanced routing spreads the deployment's token-expert pairs
        # evenly over the experts; this chip's held experts get their share
        pairs = shares * S * c.moe_top_k * c.experts_held // c.n_experts
        assert lp.macs - S * once == pairs * e
        # the shares together: the uncut layer over all shares' tokens, on
        # each of which the router, the shared and top-k experts run
        assert shares * lp.macs == shares * S * (once + c.moe_top_k * e)
        assert lw.macs == S * (once + c.moe_top_k * e)
    with pytest.raises(ValueError):
        mla_prefill_workload(dataclasses.replace(c, experts_held=5),
                             seq_len=S)


def test_deepseek_v3_prefill_at_published_widths():
    """The chain ``get_workload`` gives, against the published numbers."""
    S = 16384
    w = get_workload("deepseek_v3_prefill_16k", batch=8)
    assert (w.name, w.n, w.default_batch) == ("deepseek_v3_prefill_16k",
                                              125, 8)
    norms = 7168 + 1536 + 512
    attn = (187_105_280 + norms, 187_105_280 * S + 20_480 * S * (S + 1))
    ffn = (396_361_728, 396_361_728 * S)
    moe = (398_196_736, 398_196_736 * S)
    mtp = (102_760_448 + 2 * 7168 + 187_105_280 + norms,
           289_865_728 * S + 20_480 * S * (S + 1))
    head = (926_679_040, 2 * 926_679_040)
    want = []
    for layer in range(61):
        want += [attn, ffn if layer < 3 else moe]
    want += [mtp, moe, head]
    assert [(l.w_elems, l.macs) for l in w.layers] == want
    assert w.input_elems == S * 7168
    assert [l.out_elems for l in w.layers] == [S * 7168] * 124 + [258_560]
    skips = {i + 1: l.skip_src for i, l in enumerate(w.layers)
             if l.skip_src >= 0}
    assert skips == {123: 0, 125: 122}
    assert sorted(LM_PREFILL) == ["deepseek_v3_prefill_16k",
                                  "deepseek_v3_prefill_32k",
                                  "deepseek_v3_prefill_4k"]
    with pytest.raises(KeyError, match="deepseek_v3_prefill_4k"):
        get_workload("deepseek_v3_prefill_8k")


def test_deepseek_v3_is_mapped_only():
    """The LM substrate has no latent attention: the config is lowered
    for prefill and kept out of the substrate's list."""
    assert "deepseek_v3" in MAPPED_NAMES and "deepseek_v3" not in ARCH_NAMES
    cfg = get_config("deepseek-v3")
    assert (cfg.n_layers, cfg.n_experts, cfg.experts_held) == (61, 256, 8)
    with pytest.raises(ValueError):
        lm_workload(cfg, seq_len=4096, batch=8, mode="decode")
    qwen = lm_workload(get_config("qwen3_moe_235b"), seq_len=64, batch=4,
                       mode="prefill")
    assert qwen.n == 94 + 2                         # still one op a block


def test_126_position_chain_served_at_nmax_128():
    """``repro.serve`` with a random ``max_steps`` 128 mapper buckets the
    chain at nmax 128; every lane is the host rollout bit for bit, its
    costs are the f64 oracle's, and the engine's guard counters are the
    host rollouts'."""
    cfg = DTConfig(max_steps=128, hw_dim=repro.HW_FEATURE_DIM)
    params = dt_init(jax.random.PRNGKey(3), cfg)
    w = get_workload("deepseek_v3_prefill_4k")
    sched = repro.serve(params, cfg,
                        repro.ServingConfig(max_coalesce=4, flush_ms=0.0))
    eng = sched.engine
    assert eng.nmax_buckets == (8, 16, 32, 64, 128)
    reqs = [MapRequest(w, 8, 512 * MB, ACCEL_ZOO["datacenter"]),
            MapRequest(w, 16, 1500 * MB, ACCEL_ZOO["laptop"]),
            MapRequest(w, 4, 100 * MB, ACCEL_ZOO["datacenter"])]
    futs = [sched.submit(r) for r in reqs]
    sched.drain()
    stats = eng.stats()
    assert stats["compiled_shapes"] == [(128, 4)]
    iters = syncs = 0
    for r, fut in zip(reqs, futs):
        got = fut.result()
        env = FusionEnv(w, r.accel, batch=r.batch,
                        budget_bytes=r.budget_bytes, nmax=128)
        h = dnnfuser_infer(params, cfg, env)
        assert got.strategy.shape == (126,)
        np.testing.assert_array_equal(got.strategy, h.strategy[:126])
        assert (h.strategy[126:] == cm.SYNC).all()
        iters += h.guard_iters
        syncs += h.guard_syncs
        full = np.full(128, cm.SYNC)
        full[:126] = got.strategy
        wl = {k: np.asarray(v) for k, v in
              cm.pack_workload(w, r.accel, 128).items()}
        ref = ref_model.evaluate_ref(wl, full, r.batch, r.budget_bytes,
                                     r.accel)
        assert got.latency == pytest.approx(ref["latency"], rel=1e-5)
        assert got.peak_mem == pytest.approx(ref["peak_mem"], rel=1e-5)
        assert got.valid == ref["valid"]
    assert (stats["guard_iters"], stats["guard_syncs"]) == (iters, syncs)
    assert syncs > 0                                # the guard forced SYNCs
    assert stats["rollout_steps"] == 3 * 126
