"""Serving cells: the mapper behind ``repro.serve`` (``MapperEngine`` and
``AsyncMapperScheduler`` on the real clock), offered a closed or an open
loop from ``generate``.  Due and done times are stamped here; the
scheduler's own simulated-time stamps are not used."""
from __future__ import annotations

import time
from types import SimpleNamespace

import numpy as np

from . import check, generate, harness, work
from .harness import log, now, span

SAMPLE = 24            # served requests compared with the reference


class Serving:
    """Set-up of a serving cell: weights, the warmed stack, the traffic."""

    def __init__(self, spec, seed: int, seconds: float):
        import repro
        from repro.workloads import get_workload
        self.model = dict(spec.config["model"])
        self.model["hw_dim"] = repro.HW_FEATURE_DIM
        cfg = repro.DTConfig(
            n_blocks=self.model["n_blocks"], n_heads=self.model["n_heads"],
            d_model=self.model["d_model"], d_ff=self.model["d_ff"],
            max_steps=self.model["max_steps"], hw_dim=self.model["hw_dim"])
        self.params = harness.dt_weights(self.model, seed)
        sc = spec.serving()
        config = repro.ServingConfig(
            repair=sc["repair"], polish=sc["polish"], escalate=sc["escalate"],
            nmax_buckets=tuple(sc["nmax_buckets"]),
            max_coalesce=sc["max_coalesce"], flush_ms=sc["flush_ms"],
            replicas=sc["replicas"])
        mix = spec.mix
        names = (mix["networks"] if mix["loop"] == "closed"
                 else mix["grid"]["networks"])
        accels = (mix["accels"] if mix["loop"] == "closed"
                  else mix["grid"]["accels"])
        self.nets = {n: get_workload(n) for n in names}
        self.accels = {a: repro.ACCEL_ZOO[a] for a in accels}
        self.sched = repro.serve(self.params, cfg, config,
                                 warm=list(self.nets.values()),
                                 accel=self.accels[accels[0]])
        self.engine = self.sched.engine
        self.rng = np.random.default_rng(seed)
        self.latest: dict = {}         # condition key -> latest miss strategy
        if mix["loop"] == "open":
            self.stream = [(due, self.request(c), unseen) for due, c, unseen
                           in generate.open_stream(mix, self.rng,
                                                   seconds)]
            if mix.get("warm_grid"):
                grid = [self.request(c) for c in generate.grid(mix)]
                for req, resp in zip(grid, self.engine.serve(grid)):
                    self.latest[self.key(req)] = resp.strategy

    def request(self, c: generate.Condition):
        import repro
        return repro.MapRequest(self.nets[c.network], c.batch,
                                c.budget_bytes, self.accels[c.accel])

    def key(self, req) -> tuple:
        return (req.workload.name, int(req.batch),
                float(np.float32(req.budget_bytes)), req.accel.name)


def _width_hist(stats: dict) -> dict:
    return dict(stats["coalesce_width_hist"])


def run(spec, seed: int, seconds: float, tracing: bool, t_process: float,
        compiles) -> tuple[dict, dict, SimpleNamespace]:
    """One run of a serving cell: (end-to-end metrics, compared numbers,
    record for the per-layer readers)."""
    from repro import AdmissionError
    s = Serving(spec, seed, seconds)
    sched, engine, mix = s.sched, s.engine, spec.mix
    tracer = harness.Tracer(tracing, seconds)
    rec = SimpleNamespace(seconds=seconds, chips=spec.chips, model=s.model,
                          latencies_s=[], hit_latencies_s=[], gen_late_s=[],
                          served_flops=0.0, trace=None)
    misses: list = []                  # (request, response) served on device
    flops: dict = {}                   # chain length -> DT FLOPs of a request
    mismatch = 0
    c0 = compiles.snapshot()
    stats0 = engine.stats()

    def settle(futs):
        """Record misses first, then hits, of freshly resolved futures."""
        nonlocal mismatch
        for fut in futs:
            r = fut.response
            if not r.cached:
                misses.append((fut.request, r))
                s.latest[s.key(fut.request)] = r.strategy
                n = fut.request.workload.n
                if n not in flops:
                    flops[n] = work.dt_episode_flops(s.model, n)
                rec.served_flops += flops[n]
        for fut in futs:
            r = fut.response
            if r.cached:
                want = s.latest.get(s.key(fut.request))
                if want is not None and not np.array_equal(want, r.strategy):
                    mismatch += 1

    t0 = now()
    setup_s = t0 - t_process
    attempted = failed = 0
    if mix["loop"] == "closed":
        gen = generate.closed_conditions(mix, s.rng)
        t_end = t0 + seconds
        callers = []
        for _ in range(int(mix["callers"])):
            with span("submit"):
                callers.append((now(), sched.submit(s.request(next(gen)))))
        done = 0
        while True:
            t = now()
            tracer.tick(t - t0)
            if t >= t_end:
                break
            with span("pump"):
                sched.pump()
            fresh = []
            for i, (due, fut) in enumerate(callers):
                if not fut.done:
                    continue
                if fut.t_done <= t_end:
                    done += 1
                    rec.latencies_s.append(fut.t_done - due)
                    fresh.append(fut)
                with span("generate"):
                    req = s.request(next(gen))
                with span("submit"):
                    callers[i] = (now(), sched.submit(req))
            settle(fresh)
        attempted = done
        e2e = {"map_rps": done / seconds}
    else:
        pending: list = []
        i, n = 0, len(s.stream)
        while True:
            el = now() - t0
            tracer.tick(el)
            while i < n and s.stream[i][0] <= min(el, seconds):
                due, req, _ = s.stream[i]
                i += 1
                attempted += 1
                t_sub = now()
                rec.gen_late_s.append(t_sub - t0 - due)
                try:
                    with span("submit"):
                        fut = sched.submit(req)
                except AdmissionError:
                    failed += 1
                    rec.latencies_s.append(float("inf"))
                    continue
                if fut.done:
                    rec.latencies_s.append(fut.t_done - t0 - due)
                    rec.hit_latencies_s.append(fut.t_done - t0 - due)
                    settle([fut])
                else:
                    pending.append((due, fut))
            if el >= seconds:
                break
            if sched.queue_depth:
                with span("pump"):
                    sched.pump()
            elif i < n:
                time.sleep(max(0.0, min(s.stream[i][0], seconds) - el))
            else:
                time.sleep(max(0.0, seconds - el))
            fresh = [p for p in pending if p[1].done]
            if fresh:
                pending = [p for p in pending if not p[1].done]
                for due, fut in fresh:
                    rec.latencies_s.append(fut.t_done - t0 - due)
                settle([f for _, f in fresh])
        with span("pump"):
            sched.drain()
        for due, fut in pending:
            rec.latencies_s.append(fut.t_done - t0 - due)
        settle([f for _, f in pending])
        lat = np.asarray(rec.latencies_s)
        e2e = {"map_p95_ms": float(np.quantile(lat, 0.95)) * 1e3}
    tracer.stop()
    c1 = compiles.snapshot()
    stats1 = engine.stats()
    rec.stats0, rec.stats1 = stats0, stats1
    rec.width_hist = {w: c - _width_hist(stats0).get(w, 0)
                      for w, c in _width_hist(stats1).items()}
    log(f"window: {attempted} requests, {len(misses)} served on the device, "
        f"{failed} refused; compile events in the window {c1[0] - c0[0]} "
        f"({c1[1] - c0[1]:.3f} s), persistent-cache hits {c1[2] - c0[2]}")
    if rec.gen_late_s:
        log(f"generator lateness: p50 {np.median(rec.gen_late_s) * 1e3:.3f} "
            f"ms, p95 {np.quantile(rec.gen_late_s, 0.95) * 1e3:.3f} ms")
    rec.device = harness.device_info(harness.require_chips(spec.chips))
    rec.trace = tracer.summary()

    # the reference: a seeded sample of the device-served requests, with
    # the longest among them
    pick = np.random.default_rng([seed, 2])
    order = pick.permutation(len(misses))[:SAMPLE]
    longest = max(range(len(misses)), key=lambda j: misses[j][0].workload.n,
                  default=None)
    if longest is not None and longest not in order:
        order = np.concatenate([order[: SAMPLE - 1], [longest]])
    sample = [misses[j] for j in order]
    del s.sched, s.engine, sched, engine
    t_ref = now()
    numbers = check.check_served(
        spec.reference, [(q, r.strategy) for q, r in sample],
        [(r.latency, r.peak_mem, r.speedup, r.valid) for _, r in sample],
        s.params, s.model, spec.config["limits"], mismatch)
    numbers["illegal"] += _illegal_unsampled(spec.reference, misses,
                                              sample)
    rec.sample = [(q, r.strategy) for q, r in sample]
    rec.params = s.params
    log(f"reference: {len(sample)} requests, "
        f"{sum(q.workload.n + 1 for q, _ in sample)} served actions, "
        f"{now() - t_ref:.2f} s")
    e2e["setup_s"] = setup_s
    rec.attempted, rec.failed = attempted, failed + numbers["illegal"]
    return e2e, numbers, rec


def _illegal_unsampled(R, misses: list, sample: list) -> int:
    """Legality of every device-served answer outside the sample."""
    seen = {id(r) for _, r in sample}
    return sum(not R.legal(r.strategy, q.workload.n, int(q.batch))
               for q, r in misses if id(r) not in seen)
