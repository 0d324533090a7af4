"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret=True)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import fusion_eval, ops, ref
from repro.core import cost_model as cm
from repro.core import ref_model
from repro.core.accel import ACCEL_ZOO, PAPER_ACCEL
from repro.nn.attention import attend
from repro.workloads import resnet18, tiny_cnn, vgg16

RNG = np.random.default_rng(0)
MB = 2.0 ** 20


def _tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("B,S,Hq,Hkv,hd", [
    (1, 128, 2, 2, 64), (2, 256, 4, 2, 64), (1, 256, 8, 1, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window", [(True, -1), (False, -1),
                                           (True, 96)])
def test_flash_attention_sweep(B, S, Hq, Hkv, hd, dtype, causal, window):
    q = jnp.asarray(RNG.normal(size=(B, S, Hq, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, S, Hkv, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, S, Hkv, hd)), dtype)
    out = ops.flash_attention(q, k, v, causal=causal, window=window,
                              bq=128, bk=128, interpret=True)
    want = ref.attention_ref(q, k, v, causal=causal, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,T,Hq,Hkv,hd,kv_len", [
    (1, 1024, 4, 4, 64, 800), (2, 2048, 8, 2, 64, 2048),
    (1, 1024, 8, 1, 128, 513),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_decode_sweep(B, T, Hq, Hkv, hd, kv_len, dtype):
    q = jnp.asarray(RNG.normal(size=(B, 1, Hq, hd)), dtype)
    k = jnp.asarray(RNG.normal(size=(B, T, Hkv, hd)), dtype)
    v = jnp.asarray(RNG.normal(size=(B, T, Hkv, hd)), dtype)
    out = ops.flash_decode(q, k, v, kv_len, bk=256, interpret=True)
    want = ref.decode_ref(q, k, v, kv_len)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(want, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,T,H,n,chunk", [
    (1, 64, 2, 32, 32), (2, 130, 3, 64, 64), (1, 256, 1, 16, 64),
])
def test_wkv6_sweep(B, T, H, n, chunk):
    r, k, v = (jnp.asarray(RNG.normal(size=(B, T, H, n)), jnp.float32)
               for _ in range(3))
    w = jnp.asarray(RNG.uniform(0.75, 0.9995, size=(B, T, H, n)),
                    jnp.float32)
    u = jnp.asarray(RNG.normal(size=(H, n)), jnp.float32)
    s0 = jnp.asarray(RNG.normal(size=(B, H, n, n)), jnp.float32)
    y, sT = ops.wkv6(r, k, v, w, u, s0, chunk=chunk, interpret=True)
    yr, sr = ref.wkv6_ref(r, k, v, w, u, s0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=5e-5, atol=5e-5)
    np.testing.assert_allclose(np.asarray(sT), np.asarray(sr),
                               rtol=5e-5, atol=5e-5)


# ---------------------------------------------------------------------------
# fusion_eval: the production grid evaluator (DESIGN §13).
#
# The kernel is packed ONCE with the paper accelerator (1-byte tensors) and
# then served across the whole ACCEL_ZOO — including the 2-byte datacenter
# part, the pack-time/serve-time BPE mismatch that the pre-§13 kernel
# silently evaluated wrong.  Against the XLA evaluator the contract is
# BIT-exactness (what makes the gsampler evaluator switch corpus-neutral);
# against the independent f64 loop oracle (core.ref_model) it is the
# existing 1e-5 kernel tolerance.
# ---------------------------------------------------------------------------

_FE_WL = resnet18(batch=32)
_FE_PACKED = cm.pack_workload(_FE_WL, PAPER_ACCEL, nmax=64)
_FE_POP = np.stack([cm.random_strategy(RNG, _FE_WL.n, 64, 32)
                    for _ in range(64)])


def _assert_costout_equal(got, want):
    for field, a, b in zip(got._fields, got, want):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b),
                                      err_msg=field)


@pytest.mark.parametrize("accel", sorted(ACCEL_ZOO))
def test_fusion_eval_zoo_sweep(accel):
    """Bit parity with the XLA evaluator on every zoo accelerator, incl.
    the serve-time BPE mismatch (pack bpe=1, datacenter bpe=2)."""
    hw = ACCEL_ZOO[accel]
    out = ops.fusion_eval_population(_FE_POP, _FE_PACKED, batch=32.0,
                                     budget_bytes=20 * MB, hw=hw,
                                     interpret=True)
    want = ref.fusion_eval_ref(_FE_POP, _FE_PACKED, batch=32.0,
                               budget_bytes=20 * MB, hw=hw)
    _assert_costout_equal(out, want)


@pytest.mark.parametrize("accel", ["edge", "datacenter"])
def test_fusion_eval_matches_ref_model(accel):
    """Independent oracle: the f64 loop model, with the workload packed
    DIRECTLY at the serving accelerator's datatype — the ground truth the
    in-kernel BPE rescale must reproduce."""
    hw = ACCEL_ZOO[accel]
    wl_serve = {k: np.asarray(v)
                for k, v in cm.pack_workload(_FE_WL, hw, nmax=64).items()}
    out = ops.fusion_eval_population(_FE_POP, _FE_PACKED, batch=32.0,
                                     budget_bytes=20 * MB, hw=hw,
                                     interpret=True)
    for i in range(0, len(_FE_POP), 7):
        want = ref_model.evaluate_ref(wl_serve, _FE_POP[i], 32, 20 * MB, hw)
        for k in ("latency", "peak_mem", "traffic"):
            a = float(np.asarray(getattr(out, k))[i])
            assert abs(a - want[k]) <= 1e-5 * max(abs(want[k]), 1.0), \
                (accel, i, k, a, want[k])
        assert bool(np.asarray(out.valid)[i]) == want["valid"]
        assert int(np.asarray(out.n_groups)[i]) == want["n_groups"]


def test_fusion_eval_grid_blocks():
    """[C, POP, P] grid contract vs evaluate_grid_stats: heterogeneous
    workloads x accels x budgets, non-pow2 population, bit parity incl.
    the repair-operator stats (masked gid + per-group footprints)."""
    wl_objs = [resnet18(), vgg16(), tiny_cnn()]
    pack_accs = [PAPER_ACCEL, ACCEL_ZOO["datacenter"], ACCEL_ZOO["nano"]]
    serve_accs = [ACCEL_ZOO["datacenter"], PAPER_ACCEL, ACCEL_ZOO["mobile"]]
    wls = cm.stack_workloads([cm.pack_workload(w, a, 64)
                              for w, a in zip(wl_objs, pack_accs)])
    strats = np.stack([
        np.stack([cm.random_strategy(RNG, w.n, 64, 16) for _ in range(9)])
        for w in wl_objs])
    batches = np.full(3, 16.0, np.float32)
    budgets = np.asarray([20 * MB, 48 * MB, 4 * MB], np.float32)
    out, gid, M_g = ops.fusion_eval_grid_stats(wls, strats, batches,
                                               budgets, serve_accs,
                                               interpret=True)
    want, wgid, wMg = ref.fusion_eval_grid_ref(wls, strats, batches,
                                               budgets, serve_accs)
    _assert_costout_equal(out, want)
    np.testing.assert_array_equal(np.asarray(M_g), np.asarray(wMg))
    mask = np.asarray(wls["mask"])
    for c in range(3):                      # gid is defined under the mask
        np.testing.assert_array_equal(np.asarray(gid)[c][:, mask[c]],
                                      np.asarray(wgid)[c][:, mask[c]])
    # the plain grid entry point rides the same program
    out2 = ops.fusion_eval_grid(wls, strats, batches, budgets, serve_accs,
                                interpret=True)
    _assert_costout_equal(out2, want)


@pytest.mark.parametrize("pop_n", [1, 5])
def test_fusion_eval_nonpow2_population(pop_n):
    """Odd population sizes pad to the block width and unpad exactly."""
    w = tiny_cnn()
    wl = cm.pack_workload(w, PAPER_ACCEL, nmax=32)
    pop = np.stack([cm.random_strategy(RNG, w.n, 32, 16)
                    for _ in range(pop_n)])
    out = ops.fusion_eval_population(pop, wl, batch=16.0,
                                     budget_bytes=4 * MB, hw=PAPER_ACCEL,
                                     interpret=True)
    want = cm.evaluate_population(wl, jnp.asarray(pop), 16.0, 4 * MB,
                                  PAPER_ACCEL)
    _assert_costout_equal(out, want)


def test_fusion_eval_zero_recompiles_across_accels():
    """The accelerator is traced kernel data: sweeping the zoo at a fixed
    block shape must not grow the jit cache (the §13 serving property)."""
    cache_size = getattr(fusion_eval._fusion_eval_grid_jit, "_cache_size",
                         None)
    if cache_size is None:
        pytest.skip("jax version exposes no jit cache introspection")
    ops.fusion_eval_population(_FE_POP, _FE_PACKED, batch=32.0,
                               budget_bytes=20 * MB, hw=PAPER_ACCEL,
                               interpret=True)
    before = cache_size()
    for hw in ACCEL_ZOO.values():
        ops.fusion_eval_population(_FE_POP, _FE_PACKED, batch=32.0,
                                   budget_bytes=20 * MB, hw=hw,
                                   interpret=True)
    assert cache_size() == before, \
        "hw sweep recompiled — the accelerator became a static argument"


# ---------------------------------------------------------------------------
# attend() pallas dispatch over KV caches (the flash_decode audit): the
# cached paths carry q_offset/kv_len masking; dropping it (the pre-§13
# dispatch) read the UNWRITTEN cache tail.
# ---------------------------------------------------------------------------


def test_attend_pallas_cached_decode_masks_tail():
    """Single-token cached decode routes to flash_decode and must mask the
    garbage tail beyond kv_len (also exercises the bk > T clamp)."""
    B, T, Hq, Hkv, hd = 2, 60, 4, 2, 16
    kv_len = 37
    q = jnp.asarray(RNG.normal(size=(B, 1, Hq, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, T, Hkv, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, T, Hkv, hd)), jnp.float32)
    k = k.at[:, kv_len:].set(1e6)            # poison the unwritten tail
    v = v.at[:, kv_len:].set(-1e6)
    for q_off in (kv_len - 1, 20):          # last-token and mid-cache query
        ox = attend(q, k, v, causal=True, q_offset=q_off, kv_len=kv_len,
                    impl="xla")
        op = attend(q, k, v, causal=True, q_offset=q_off, kv_len=kv_len,
                    impl="pallas")
        np.testing.assert_allclose(np.asarray(op), np.asarray(ox),
                                   rtol=2e-5, atol=2e-5)


def test_attend_pallas_cached_append_bitexact_xla():
    """Multi-token cache appends (the dt_decode_step shape: 2-3 tokens per
    step) have no pallas kernel — the dispatch must fall back to the exact
    XLA masking math, keeping cached decode == full forward bit-for-bit
    whether or not the pallas path is selected."""
    B, T, Hq, Hkv, hd = 2, 60, 4, 4, 16
    k = jnp.asarray(RNG.normal(size=(B, T, Hkv, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, T, Hkv, hd)), jnp.float32)
    for S, kv_len in ((2, 2), (3, 17), (3, 60)):
        q = jnp.asarray(RNG.normal(size=(B, S, Hq, hd)), jnp.float32)
        ox = attend(q, k, v, causal=True, q_offset=kv_len - S,
                    kv_len=kv_len, impl="xla")
        op = attend(q, k, v, causal=True, q_offset=kv_len - S,
                    kv_len=kv_len, impl="pallas")
        np.testing.assert_array_equal(np.asarray(op), np.asarray(ox))


def test_flash_decode_cache_not_multiple_of_block():
    """bk > T and T % bk != 0 must clamp/pad instead of dropping tail keys."""
    B, T, Hq, Hkv, hd = 1, 72, 4, 2, 32
    q = jnp.asarray(RNG.normal(size=(B, 1, Hq, hd)), jnp.float32)
    k = jnp.asarray(RNG.normal(size=(B, T, Hkv, hd)), jnp.float32)
    v = jnp.asarray(RNG.normal(size=(B, T, Hkv, hd)), jnp.float32)
    for kv_len, bk in ((72, 512), (50, 32), (7, 16)):
        out = ops.flash_decode(q, k, v, kv_len, bk=bk, interpret=True)
        want = ref.decode_ref(q, k, v, kv_len)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   rtol=2e-5, atol=2e-5)


def test_model_pallas_path_matches_xla():
    """attn_impl='pallas' end-to-end equals the XLA path (reduced arch)."""
    from repro.configs import get_config
    from repro.models import get_model
    cfg = get_config("qwen3_8b", reduced=True)
    model = get_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
    toks = jnp.asarray(RNG.integers(0, cfg.vocab, (2, 128)), jnp.int32)
    batch = {"tokens": toks, "labels": toks}
    lx, _ = model.forward(params, cfg, batch, impl="xla")
    lp, _ = model.forward(params, cfg, batch, impl="pallas")
    np.testing.assert_allclose(np.asarray(lx), np.asarray(lp),
                               rtol=2e-4, atol=2e-4)


# --- lowering mode and block width: chosen from the platform, no fallback ---


def _spy_grid_jit(monkeypatch):
    """Record the static (bp, interpret) each call hands the jitted kernel."""
    seen = []
    real = fusion_eval._fusion_eval_grid_jit

    def spy(*args, bp, interpret):
        seen.append((bp, interpret))
        return real(*args, bp=bp, interpret=interpret)
    monkeypatch.setattr(fusion_eval, "_fusion_eval_grid_jit", spy)
    return seen


def test_cpu_platform_resolves_to_interpret(monkeypatch):
    """interpret=None on the CPU backend interprets, and gives the same
    bits as an explicit interpret=True."""
    assert jax.default_backend() == "cpu"
    seen = _spy_grid_jit(monkeypatch)
    auto = ops.fusion_eval_population(_FE_POP, _FE_PACKED, batch=32.0,
                                      budget_bytes=20 * MB, hw=PAPER_ACCEL)
    want = ops.fusion_eval_population(_FE_POP, _FE_PACKED, batch=32.0,
                                      budget_bytes=20 * MB, hw=PAPER_ACCEL,
                                      interpret=True)
    assert [i for _, i in seen] == [True, True]
    _assert_costout_equal(auto, want)


def test_compiled_request_on_cpu_raises():
    """An explicit interpret=False on the CPU raises Pallas's own error;
    nothing falls back to interpret mode."""
    with pytest.raises(ValueError, match="interpret mode"):
        ops.fusion_eval_population(_FE_POP, _FE_PACKED, batch=32.0,
                                   budget_bytes=20 * MB, hw=PAPER_ACCEL,
                                   interpret=False)


@pytest.mark.parametrize("pop_n,width", [(5, 8), (64, 64), (200, 128)])
def test_default_block_width_is_fixed_rule(monkeypatch, pop_n, width):
    """bp=None takes _block_size's width: one pow2 block up to 128 lanes,
    128-lane blocks beyond (the TPU tiling rule for a partial block)."""
    seen = _spy_grid_jit(monkeypatch)
    ops.fusion_eval_population(_FE_POP[np.arange(pop_n) % len(_FE_POP)],
                               _FE_PACKED, batch=32.0, budget_bytes=20 * MB,
                               hw=PAPER_ACCEL)
    assert seen == [(fusion_eval._block_size(pop_n), True)]
    assert fusion_eval._block_size(pop_n) == width


def test_gsampler_pallas_evaluator_inside_jit_runs_on_cpu():
    """G-Sampler asks for the Pallas evaluator from inside its jitted
    body; the lowering mode must be decided there from the platform, with
    no probe or tuner running on tracers."""
    from repro.core import GSamplerConfig, gsampler_search_grid
    cfg = GSamplerConfig(population=8, generations=2, elite=2,
                         repair_tries=1, seed=0)
    res = gsampler_search_grid([tiny_cnn()], [PAPER_ACCEL], [8.0],
                               [4 * MB], nmax=8, cfg=cfg, top_k=2,
                               evaluator="pallas")
    assert np.isfinite(np.asarray(res.latency)).all()
    assert np.asarray(res.valid).any()


# ---------------------------------------------------------------------------
# optimality oracle (DESIGN §16): every production evaluator — XLA
# evaluate_population, the Pallas kernel (explicit interpret and the
# platform-resolved default), and the prefix-scan serving evaluator — is
# pinned against the exact f64 brute-force optimum on the shared
# adversarial workload set.
# ---------------------------------------------------------------------------

import _adversarial as adv
from repro.core import optimal as op


def _oracle_rows(wl_np, batch, nmax):
    """bf-optimal strategy + all-sync + a strided slice of the full space."""
    n = int(wl_np["n"])
    pop = op.enumerate_strategies(n, batch, nmax)
    idx = np.unique(np.linspace(0, len(pop) - 1, 14).astype(int))
    return pop[idx]


@pytest.mark.parametrize("case", adv.cases(), ids=lambda c: c[0])
def test_evaluators_agree_with_f64_oracle_adversarial(case):
    """On adversarial chains all four evaluator ports agree with the f64
    loop oracle within kernel tolerance, and their best valid row equals
    the certified brute-force optimum."""
    name, wl, batch, budget, pack_hw, serve_hw = case
    wl_np = adv.packed(wl, pack_hw)
    bf = op.brute_force_optimal(wl_np, batch, budget, serve_hw)
    pop = np.concatenate([bf.strategy[None], _oracle_rows(wl_np, batch,
                                                          adv.NMAX)])
    wl_serve = op.scaled_wl_np(wl_np, serve_hw)

    outs = {
        "xla": cm.evaluate_population(wl_np, jnp.asarray(pop), float(batch),
                                      float(budget), serve_hw),
        "pallas": ops.fusion_eval_population(pop, wl_np, batch=float(batch),
                                             budget_bytes=float(budget),
                                             hw=serve_hw, interpret=True),
        "pallas_auto": ops.fusion_eval_population(
            pop, wl_np, batch=float(batch), budget_bytes=float(budget),
            hw=serve_hw),                    # platform-resolved default
    }
    scans = [cm.prefix_scan(wl_np, jnp.asarray(s), float(batch),
                            float(budget), serve_hw)[1] for s in pop]
    outs["prefix_scan"] = cm.CostOut(*(np.stack([np.asarray(getattr(f, k))
                                                 for f in scans])
                                       for k in cm.CostOut._fields))

    boundary = name.startswith("boundary")
    for port, out in outs.items():
        lat = np.asarray(out.latency, np.float64)
        pk = np.asarray(out.peak_mem, np.float64)
        va = np.asarray(out.valid, bool)
        best = np.inf
        for i, s in enumerate(pop):
            want = ref_model.evaluate_ref(wl_serve, s, batch, budget,
                                          serve_hw)
            assert abs(lat[i] - want["latency"]) <= \
                1e-5 * max(abs(want["latency"]), 1e-30), (port, name, i)
            assert abs(pk[i] - want["peak_mem"]) <= \
                1e-5 * max(abs(want["peak_mem"]), 1.0), (port, name, i)
            at_edge = abs(want["peak_mem"] - budget) <= 1e-4 * max(budget,
                                                                   1.0)
            if not (boundary and at_edge):
                assert bool(va[i]) == want["valid"], (port, name, i)
            if va[i] and want["valid"]:
                best = min(best, lat[i])
        if bf.valid and not boundary:
            assert abs(best - bf.latency) <= 1e-5 * abs(bf.latency), \
                (port, name, best, bf.latency)
