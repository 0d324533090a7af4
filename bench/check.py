"""The comparison that decides ``correct``: what the timed path served,
against the plain reference that the cell's configuration names
(``harness.Spec.reference``: ``bench/reference.py`` unless the
configuration's ``reference`` names another file), passed in as ``R``,
each number beside its limit.  The limits live in the configuration's
file, under ``limits``, with the readings they were set from in PERF.md.

Numbers compared:

- ``pred_gap`` (served models): over a seeded sample of the requests the
  window served on the device, the widest gap between the reference
  decision transformer's head output and the outputs that would have given
  each served action, in encoded-action units (one micro-batch step is
  1/batch).  The reference runs the full-sequence forward along the served
  trajectory, with the environment and the guard of the reference.
- ``cost_rel``: the widest relative gap between the served latency, peak
  memory and no-fusion baseline (through the speedup) of a strategy and
  the f64 reference's.
- ``valid_flips``: answers whose validity disagrees with the reference
  where the reference's peak is farther from the budget than
  ``cost_rel``'s limit.  Exact: limit 0.
- ``illegal``: answers that are no strategy of the request's chain.
  Exact: limit 0.
- ``hit_mismatch`` (served models): strategy-cache hits whose strategy is
  not the one its miss computed.  Exact: limit 0.
"""
from __future__ import annotations

import numpy as np


def _fits_variants(peaks_t: np.ndarray, budget: float, eps: float) -> list:
    """The guard's fit vector at one step; two where a probe's peak lies
    within ``eps`` of the budget, where f32 and f64 may decide apart."""
    exact = peaks_t <= budget
    edge = np.abs(peaks_t - budget) <= eps * budget
    if not edge.any():
        return [exact]
    return [exact, exact | edge]


def _encode(strategy: np.ndarray, batch: int) -> np.ndarray:
    return np.where(strategy < 0, -0.5, strategy / float(batch))


def prepare(R, samples: list, eps: float) -> list:
    """Reference environment along each served trajectory: observations,
    encoded actions and the guard's fit vectors."""
    out = []
    for req, strat in samples:
        batch = int(req.batch)
        budget = float(np.float32(req.budget_bytes))
        arr = R.layer_arrays(req.workload, req.accel.bytes_per_elem)
        n = int(arr["n"])
        s = np.asarray(strat, np.int64)
        item = dict(req=req, strategy=s, arr=arr, batch=batch,
                    budget=budget, n=n, legal=R.legal(s, n, batch))
        if item["legal"]:
            rtg, states = R.observations(arr, s, batch, budget, req.accel)
            peaks = R.probe_peaks(arr, s, batch, req.accel)
            item.update(
                rtg=rtg, states=states, actions=_encode(s, batch),
                fits=[_fits_variants(peaks[t], budget, eps)
                      for t in range(n + 1)])
        out.append(item)
    return out


def head_outputs(R, items: list, params, model: dict, quant: bool
                 ) -> np.ndarray:
    """[K, max_steps] teacher-forced head outputs of the reference DT."""
    import jax.numpy as jnp
    T = model["max_steps"]
    K = len(items)
    rtg = np.zeros((K, T), np.float32)
    states = np.zeros((K, T, 8), np.float32)
    actions = np.zeros((K, T), np.float32)
    hw = np.zeros((K, model["hw_dim"]), np.float32)
    for k, it in enumerate(items):
        if not it["legal"]:
            continue
        n1 = it["n"] + 1
        rtg[k, :n1] = it["rtg"]
        states[k, :n1] = it["states"]
        actions[k, :n1] = it["actions"]
        hw[k] = R.hw_features(it["req"].accel)
    y = R.dt_forward_jit(params, jnp.asarray(rtg), jnp.asarray(states),
                         jnp.asarray(actions), jnp.asarray(hw),
                         n_heads=model["n_heads"], quant=quant)
    return np.asarray(y, np.float64)


def pred_gap(R, items: list, y_ref: np.ndarray, y_other=None) -> float:
    """Widest gap of the served actions (or, with ``y_other``, of the
    actions those head outputs give) from the reference's outputs."""
    worst = 0.0
    for k, it in enumerate(items):
        if not it["legal"]:
            continue
        for t in range(it["n"] + 1):
            if y_other is None:
                a = int(it["strategy"][t])
            else:
                a = R.first_action(float(y_other[k, t]), t, it["batch"],
                                   it["fits"][t][0])
            worst = max(worst, R.action_gap(float(y_ref[k, t]), a, t,
                                            it["batch"], it["fits"][t]))
    return worst


def cost_numbers(R, items: list, served: list, eps: float, dt=np.float64
                 ) -> dict:
    """``cost_rel`` and ``valid_flips`` of served (latency, peak, speedup,
    valid) tuples against the reference evaluated in ``dt``; with ``dt``
    below float64 the served numbers are the f64 reference's own, so the
    gap is that of a lower-precision evaluator."""
    rel, flips = 0.0, 0
    for it, (lat, peak, speedup, valid) in zip(items, served):
        if not it["legal"]:
            continue
        S = it["strategy"][None]
        ref = R.evaluate(it["arr"], S, it["batch"], it["req"].accel)
        base = R.baseline(it["arr"], it["batch"], it["req"].accel)
        ref_sp = base / ref["latency"][0]
        if dt is not np.float64:
            low = R.evaluate(it["arr"], S, it["batch"], it["req"].accel, dt)
            lat, peak = low["latency"][0], low["peak"][0]
            speedup = base / lat
            valid = peak <= it["budget"]
        for got, want in ((lat, ref["latency"][0]), (peak, ref["peak"][0]),
                          (speedup, ref_sp)):
            rel = max(rel, abs(float(got) - want) / max(abs(want), 1e-30))
        ref_peak = ref["peak"][0]
        if (bool(valid) != bool(ref_peak <= it["budget"])
                and abs(ref_peak - it["budget"]) > eps * it["budget"]):
            flips += 1
    return {"cost_rel": rel, "valid_flips": flips}


def check_served(R, samples: list, served: list, params, model: dict,
                 limits: dict, hit_mismatch: int) -> dict:
    """The numbers of a served-model cell.  ``samples`` are (request,
    strategy) pairs and ``served`` the matching (latency, peak, speedup,
    valid) tuples."""
    items = prepare(R, samples, limits["cost_rel"])
    y_ref = head_outputs(R, items, params, model, quant=False)
    numbers = {"pred_gap": pred_gap(R, items, y_ref),
               **cost_numbers(R, items, served, limits["cost_rel"]),
               "illegal": sum(not it["legal"] for it in items),
               "hit_mismatch": hit_mismatch}
    return numbers


def control_served(R, samples: list, params, model: dict, limits: dict
                   ) -> dict:
    """The control's numbers on the same requests: the reference DT with
    float8 matmul operands in the program's place (the action it puts
    first at each step of the served trajectory), and the cost model in
    bfloat16."""
    items = prepare(R, samples, limits["cost_rel"])
    y_ref = head_outputs(R, items, params, model, quant=False)
    y_low = head_outputs(R, items, params, model, quant=True)
    return {"pred_gap": pred_gap(R, items, y_ref, y_low),
            **cost_numbers(R, items, [(None,) * 4] * len(items),
                           limits["cost_rel"], dt=R.BF16)}


def check_search(R, samples: list, limits: dict, dt=np.float64) -> dict:
    """The numbers of a search cell.  ``samples``: (condition request,
    elite strategies [K, P], latency [K], peak [K], speedup [K], valid [K])
    per condition.  With ``dt`` below float64 the elites' costs are the
    reference's own in ``dt`` (the control)."""
    rel, flips, illegal = 0.0, 0, 0
    eps = limits["cost_rel"]
    for req, strats, lat, peak, speedup, valid in samples:
        batch = int(req.batch)
        budget = float(np.float32(req.budget_bytes))
        arr = R.layer_arrays(req.workload, req.accel.bytes_per_elem)
        n = int(arr["n"])
        S = np.asarray(strats, np.int64)
        ok = np.array([R.legal(s[: n + 1], n, batch)
                       and bool(np.all(s[n + 1:] == R.SYNC)) for s in S])
        illegal += int((~ok).sum())
        S = S[ok]
        if not len(S):
            continue
        ref = R.evaluate(arr, S, batch, req.accel)
        base = R.baseline(arr, batch, req.accel)
        got_lat, got_peak = np.asarray(lat)[ok], np.asarray(peak)[ok]
        got_sp, got_valid = np.asarray(speedup)[ok], np.asarray(valid)[ok]
        if dt is not np.float64:
            low = R.evaluate(arr, S, batch, req.accel, dt)
            got_lat, got_peak = low["latency"], low["peak"]
            got_sp = base / got_lat
            got_valid = got_peak <= budget
        for got, want in ((got_lat, ref["latency"]), (got_peak, ref["peak"]),
                          (got_sp, base / ref["latency"])):
            rel = max(rel, float(np.max(np.abs(got - want)
                                        / np.maximum(np.abs(want), 1e-30))))
        far = np.abs(ref["peak"] - budget) > eps * budget
        flips += int(np.sum((got_valid != (ref["peak"] <= budget)) & far))
    return {"cost_rel": rel, "valid_flips": flips, "illegal": illegal}
