"""The async serving front door (DESIGN.md §14).

Pins the scheduler contracts:

 - **determinism**: responses from async/coalesced serving are bit-
   identical to per-request serving, independent of arrival order, flush
   deadline and wave width — and so are the strategy-cache contents
   after a drain (same unique conditions, same solved entries);
 - **continuous batching mechanics**: width-triggered flushes under
   load, deadline-triggered flushes for stragglers, cache hits resolved
   at submit (never queued), bounded queue with admission rejection;
 - **oversized ticks** (the warmup escape hatch): a tick wider than the
   warmed set chunks into warmed pow2 programs — zero new compiles, and
   every response still bit-exact with solo serving.
"""
import jax
import numpy as np
import pytest

from repro.core import ACCEL_ZOO, DTConfig, dt_init
from repro.core import infer as infer_mod
from repro.serving import (AdmissionError, AsyncMapperScheduler, MapperEngine,
                           MapRequest, pow2_chunks)
from repro.workloads import resnet18, tiny_cnn, vgg16

MB = 2 ** 20

CFG = DTConfig(max_steps=20)
PARAMS = dt_init(jax.random.PRNGKey(2), CFG)


def _stream():
    """A small mixed stream with duplicate conditions across nets/accels."""
    nets = [vgg16(), resnet18(), tiny_cnn()]
    accs = [ACCEL_ZOO["edge"], ACCEL_ZOO["mobile"]]
    reqs = [MapRequest(nets[i % 3], 16 << (i % 2), (8 + (i % 5)) * MB,
                       accs[i % 2]) for i in range(10)]
    return reqs + reqs[:4]                       # 4 exact repeats


def _assert_same_response(a, b):
    assert (a.strategy == b.strategy).all()
    assert a.latency == b.latency and a.peak_mem == b.peak_mem
    assert a.valid == b.valid


def _snapshot_equal(s1: dict, s2: dict):
    assert s1.keys() == s2.keys()
    for k, (st1, *rest1) in s1.items():
        st2, *rest2 = s2[k]
        assert (np.asarray(st1) == np.asarray(st2)).all(), k
        assert rest1 == rest2, k


def test_pow2_chunks():
    assert pow2_chunks(23, 8) == (8, 8, 7)
    assert pow2_chunks(8, 8) == (8,)
    assert pow2_chunks(3, 8) == (3,)
    assert pow2_chunks(9, 7) == (8, 1)           # cap rounds up to pow2
    with pytest.raises(ValueError):
        pow2_chunks(0, 8)


def test_scheduler_bit_identical_to_solo_serving_under_permutation():
    """S3: permuted arrival orders, different flush deadlines and wave
    widths, all against one per-request baseline — every response and the
    drained cache contents must be bit-identical."""
    reqs = _stream()
    solo = MapperEngine(PARAMS, CFG)
    base = [solo.serve_one(r) for r in reqs]

    rng = np.random.default_rng(0)
    orders = [list(range(len(reqs))), list(rng.permutation(len(reqs))),
              list(rng.permutation(len(reqs)))]
    configs = [dict(flush_ms=0.0, max_wave=4), dict(flush_ms=5.0, max_wave=4),
               dict(flush_ms=1e3, max_wave=2), dict(flush_ms=1e3, max_wave=8)]
    snap = None
    for order, kw in zip(orders + orders[:1], configs):
        eng = MapperEngine(PARAMS, CFG)
        sched = AsyncMapperScheduler(eng, **kw)
        futs = {}
        for t, i in enumerate(order):
            futs[i] = sched.submit(reqs[i], now=t * 1e-3)
            sched.pump(now=t * 1e-3)
        sched.drain(now=len(order) * 1e-3)
        for i, b in enumerate(base):
            _assert_same_response(futs[i].result(), b)
        s = eng.strategies.snapshot()
        if snap is None:
            snap = s
        else:
            _snapshot_equal(snap, s)             # identical cache contents
    _snapshot_equal(snap, solo.strategies.snapshot())


def test_scheduler_width_and_deadline_flushes():
    eng = MapperEngine(PARAMS, CFG)
    sched = AsyncMapperScheduler(eng, flush_ms=10.0, max_wave=2)
    a = sched.submit(MapRequest(tiny_cnn(), 16, 8 * MB, ACCEL_ZOO["edge"]),
                     now=0.0)
    sched.pump(now=0.001)
    assert not a.done and sched.queue_depth == 1     # lone request waits
    b = sched.submit(MapRequest(tiny_cnn(), 32, 9 * MB, ACCEL_ZOO["edge"]),
                     now=0.002)
    sched.pump(now=0.002)                            # 2 unique = full wave
    assert a.done and b.done and sched.flushes["width"] == 1
    assert a.latency_s > 0 and a.t_done == b.t_done  # same tick
    # a straggler flushes on deadline, not width
    c = sched.submit(MapRequest(tiny_cnn(), 16, 11 * MB, ACCEL_ZOO["edge"]),
                     now=0.1)
    sched.pump(now=0.105)
    assert not c.done
    sched.pump(now=0.111)
    assert c.done and sched.flushes["deadline"] == 1
    # an exact duplicate of a solved condition resolves AT SUBMIT
    d = sched.submit(MapRequest(tiny_cnn(), 16, 8 * MB, ACCEL_ZOO["edge"]),
                     now=0.2)
    assert d.done and d.result().cached
    assert sched.resolved_at_submit == 1
    _assert_same_response(d.result(), a.result())


def test_scheduler_queue_wait_and_spans():
    """``queue_wait_s`` sums flush start minus submit over the flushed
    requests, on the scheduler's clock; every pump and every flushed lane
    is a span."""
    eng = MapperEngine(PARAMS, CFG)
    sched = AsyncMapperScheduler(eng, flush_ms=10.0, max_wave=8)
    sched.submit(MapRequest(tiny_cnn(), 16, 8 * MB, ACCEL_ZOO["edge"]),
                 now=0.0)
    sched.submit(MapRequest(tiny_cnn(), 32, 9 * MB, ACCEL_ZOO["edge"]),
                 now=0.004)
    sched.pump(now=0.005)                        # nothing due yet
    sched.pump(now=0.020)                        # one lane, on deadline
    st = sched.stats()
    assert st["queued"] == 2 and st["flushes"]["deadline"] == 1
    assert st["queue_wait_s"] == pytest.approx(0.020 + 0.016)
    assert st["spans"]["scheduler.pump"]["count"] == 2
    assert st["spans"]["scheduler.flush"]["count"] == 1
    # the flushes' engine time lies inside the pumps
    assert (eng.stats()["spans"]["engine.serve"]["seconds"]
            <= st["spans"]["scheduler.pump"]["seconds"])


def test_scheduler_admission_control():
    eng = MapperEngine(PARAMS, CFG)
    sched = AsyncMapperScheduler(eng, max_queue=2, flush_ms=1e3, max_wave=8)
    r = [MapRequest(tiny_cnn(), 16, (8 + i) * MB, ACCEL_ZOO["edge"])
         for i in range(3)]
    sched.submit(r[0], now=0.0)
    sched.submit(r[1], now=0.0)
    with pytest.raises(AdmissionError):
        sched.submit(r[2], now=0.0)
    assert sched.rejected == 1 and sched.submitted == 2
    sched.drain(now=0.01)                        # frees the queue
    fut = sched.submit(r[2], now=0.02)           # admitted after backpressure
    sched.drain(now=0.03)
    assert fut.done and sched.queue_depth == 0


def test_oversized_tick_chunks_to_warmed_programs():
    """S1: warmup covers ticks up to 8 lanes; a 23-request tick must chunk
    into (8, 8, 7->pad 8) — ZERO new compiles (engine counter AND jax's
    own jit cache) and every response bit-exact with solo serving."""
    eng = MapperEngine(PARAMS, CFG, max_coalesce=16)
    eng.warmup([tiny_cnn()], ACCEL_ZOO["edge"], max_tick=8)
    assert eng.chunk_cap == 8
    jit_cache = getattr(infer_mod._fused_batch, "_cache_size", None)
    jit_before = jit_cache() if jit_cache else None
    before = eng.compile_count
    reqs = [MapRequest(tiny_cnn(), 1 + i % 4, (6 + i) * MB, ACCEL_ZOO["edge"])
            for i in range(23)]
    out = eng.serve(reqs)
    assert eng.compile_count == before, "oversized tick recompiled"
    if jit_cache is not None:
        assert jit_cache() == jit_before
    hist = eng.coalesce_hist
    assert hist.get(8, 0) >= 2 and hist.get(7, 0) == 1
    solo = MapperEngine(PARAMS, CFG)
    for req, resp in zip(reqs, out):
        _assert_same_response(resp, solo.serve_one(req))
