"""Cost-model unit + property tests: the vectorized jnp model must agree
with the independent loop-based reference, and satisfy the fusion-physics
invariants the paper's results rest on."""
import numpy as np
import jax.numpy as jnp
import pytest

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:          # degrade gracefully without the 'test' extra
    HAVE_HYPOTHESIS = False

from repro.workloads import vgg16, resnet18, mobilenet_v2, get_workload
from repro.core import cost_model as cm
from repro.core import ref_model
from repro.core.accel import PAPER_ACCEL, AccelConfig

HW = PAPER_ACCEL
MB = 2 ** 20
WL = {w.name: w for w in (vgg16(), resnet18(), mobilenet_v2())}
PACKED = {n: cm.pack_workload(w, HW, 64) for n, w in WL.items()}
PACKED_NP = {n: {k: np.asarray(v) for k, v in p.items()}
             for n, p in PACKED.items()}


# The property checks run under hypothesis when the 'test' extra is
# installed (CI), and against a fixed seeded-numpy sweep otherwise — the
# bare install no longer silently skips them (the pre-§16 skip-debt).


def _check_matches_reference(s, wname):
    out = cm.evaluate(PACKED[wname], jnp.asarray(s), 64.0, 20 * MB, HW)
    ref = ref_model.evaluate_ref(PACKED_NP[wname], s, 64, 20 * MB, HW)
    for k in ("latency", "peak_mem", "traffic"):
        a, b = float(getattr(out, k)), ref[k]
        assert abs(a - b) <= 1e-5 * max(abs(b), 1.0), (k, a, b)
    assert bool(out.valid) == ref["valid"]
    assert int(out.n_groups) == ref["n_groups"]


def _check_invariants(s, wname):
    """Physics: latency/peak positive; fusing never increases off-chip
    traffic at fixed micro-batches vs all-sync; peak >= the largest
    staged activation term."""
    w = WL[wname]
    out = cm.evaluate(PACKED[wname], jnp.asarray(s), 64.0, 20 * MB, HW)
    assert float(out.latency) > 0 and float(out.peak_mem) >= 0
    # full fusion at full-batch micro-batches (weights fetched once, all
    # intermediates staged) is the traffic lower bound vs all-sync
    s_fused = np.full(64, cm.SYNC, np.int32)
    s_fused[: w.n + 1] = 64
    out_f = cm.evaluate(PACKED[wname], jnp.asarray(s_fused), 64.0,
                        20 * MB, HW)
    s_allsync = np.full(64, cm.SYNC, np.int32); s_allsync[0] = 1
    out_s = cm.evaluate(PACKED[wname], jnp.asarray(s_allsync), 64.0,
                        20 * MB, HW)
    assert float(out_f.traffic) <= float(out_s.traffic) * (1 + 1e-6)


def _seeded_strategy(rng, n, batch=64):
    vals = np.where(rng.random(n + 1) < 0.4, cm.SYNC,
                    rng.integers(1, batch + 1, size=n + 1))
    s = np.full(64, cm.SYNC, np.int32)
    s[: n + 1] = vals
    if s[0] < 1:
        s[0] = 1
    return s


if HAVE_HYPOTHESIS:
    def _rand_strategy(data, n, batch=64):
        vals = data.draw(st.lists(
            st.one_of(st.just(-1), st.integers(1, batch)),
            min_size=n + 1, max_size=n + 1))
        s = np.full(64, cm.SYNC, np.int32)
        s[: n + 1] = vals
        if s[0] < 1:
            s[0] = 1
        return s

    @settings(max_examples=60, deadline=None)
    @given(data=st.data(), wname=st.sampled_from(sorted(WL)))
    def test_jnp_matches_reference(data, wname):
        _check_matches_reference(_rand_strategy(data, WL[wname].n), wname)

    @settings(max_examples=40, deadline=None)
    @given(data=st.data(), wname=st.sampled_from(sorted(WL)))
    def test_invariants(data, wname):
        _check_invariants(_rand_strategy(data, WL[wname].n), wname)
else:
    @pytest.mark.parametrize("wname", sorted(WL))
    def test_jnp_matches_reference(wname):
        rng = np.random.default_rng(7)
        for _ in range(20):
            _check_matches_reference(_seeded_strategy(rng, WL[wname].n),
                                     wname)

    @pytest.mark.parametrize("wname", sorted(WL))
    def test_invariants(wname):
        rng = np.random.default_rng(11)
        for _ in range(13):
            _check_invariants(_seeded_strategy(rng, WL[wname].n), wname)


def test_host_grid_pack_equals_device_pack():
    """The host packer's grid equals the stacked device packings leaf by
    leaf, across networks of different ``n`` and accelerators of different
    bytes/elem; the numpy hardware stack equals ``stack_hw``."""
    from repro.core.accel import (ACCEL_ZOO, HW_FIELDS, stack_hw,
                                  stack_hw_host)
    from repro.workloads import tiny_cnn
    edge, dc = ACCEL_ZOO["edge"], ACCEL_ZOO["datacenter"]
    assert edge.bytes_per_elem != dc.bytes_per_elem
    wls = [tiny_cnn(), resnet18(), tiny_cnn(), resnet18()]
    hws = [edge, edge, dc, dc]
    host = cm.pack_grid_host(wls, hws, 32)
    dev = cm.stack_workloads([cm.pack_workload(w, h, 32)
                              for w, h in zip(wls, hws)])
    assert list(host) == list(dev)
    dtypes = dict(SKIP=np.int32, n=np.int32, mask=np.bool_)
    for k, d in dev.items():
        d = np.asarray(d)
        assert isinstance(host[k], np.ndarray), k
        assert host[k].dtype == d.dtype == dtypes.get(k, np.float32), k
        assert host[k].shape == d.shape, k
        np.testing.assert_array_equal(host[k], d, err_msg=k)
    assert host["n"].tolist() == [w.n for w in wls]
    assert host["BPE"].tolist() == [h.bytes_per_elem for h in hws]

    hv, dv = stack_hw_host(hws), stack_hw(hws, len(hws))
    for f in HW_FIELDS:
        a, b = getattr(hv, f), np.asarray(getattr(dv, f))
        assert isinstance(a, np.ndarray), f
        assert a.dtype == b.dtype == np.float32 and a.shape == b.shape, f
        np.testing.assert_array_equal(a, b, err_msg=f)


def test_baseline_matches_ref():
    for n, w in WL.items():
        b = cm.baseline_no_fusion(PACKED[n], 64.0, HW)
        rb = ref_model.baseline_ref(PACKED_NP[n], 64, HW)
        assert abs(float(b.latency) - rb) < 1e-6 * rb


def test_prefix_trace_full_equals_evaluate():
    w = WL["resnet18"]
    rng = np.random.default_rng(0)
    s = cm.random_strategy(rng, w.n, 64, 64)
    tr = cm.prefix_trace(PACKED["resnet18"], jnp.asarray(s), 64.0,
                         20 * MB, HW)
    full = cm.evaluate(PACKED["resnet18"], jnp.asarray(s), 64.0, 20 * MB, HW)
    # entry n+1 applies positions < n+1 == the whole strategy
    assert np.isclose(float(tr.latency[w.n + 1]), float(full.latency),
                      rtol=1e-6)


def test_memory_monotone_in_microbatch():
    """Raising one staged micro-batch can only raise group peak memory."""
    w = WL["vgg16"]
    s = np.full(64, cm.SYNC, np.int32)
    s[: w.n + 1] = 4
    lo = cm.evaluate(PACKED["vgg16"], jnp.asarray(s), 64.0, 64 * MB, HW)
    s2 = s.copy(); s2[3] = 32
    hi = cm.evaluate(PACKED["vgg16"], jnp.asarray(s2), 64.0, 64 * MB, HW)
    assert float(hi.peak_mem) >= float(lo.peak_mem)


def test_speedup_band_matches_paper_case1():
    """Faithfulness anchor: G-Sampler-quality strategies on VGG16 case-1
    land near the paper's 1.19x (band check, not exact-match)."""
    from repro.core import FusionEnv, gsampler_search, GSamplerConfig
    env = FusionEnv(WL["vgg16"], HW, batch=64, budget_bytes=20 * MB)
    res = gsampler_search(env, GSamplerConfig(generations=25, seed=0))
    assert res.valid
    assert 1.05 <= res.speedup <= 1.6, res.speedup
