"""Analytical layer-fusion cost model (paper §5.1 "Cost Model").

Maps (workload, batch, HW, fusion strategy) -> (latency, peak on-chip
memory, off-chip traffic).  Semantics are specified in DESIGN.md §3; in
short, a strategy ``[mb_0, mb_1, ..., mb_N]`` (``-1`` = sync) segments the
chain into fused groups; within a group weights are resident and
intermediate activations are staged on-chip at per-layer micro-batch
granularity, so only group inputs/outputs (and group weights, once) touch
off-chip memory.  Group latency is the roofline max of compute / off-chip /
on-chip time plus per-wave pipeline and per-group sync overheads.

Everything is fixed-shape ``jnp`` so a whole GA population (and a batch of
memory conditions) evaluates in a single jitted/vmapped call — this is the
search hot loop the Pallas kernel ``kernels/fusion_eval`` also implements.
The population/grid entry points dispatch between the two backends via
their ``evaluator`` kwarg ("xla" | "pallas", DESIGN.md §13); both funnel
their per-group decompositions through :func:`finalize_groups`, so on the
CPU container (interpret mode) the backends are bit-identical and the
G-Sampler teacher pipeline emits the same corpus on either.

The accelerator is a CONDITION, not a compile-time constant (DESIGN.md
§11): every entry point takes ``hw`` as either a host ``AccelConfig`` or a
traced ``accel.HwVec`` pytree, so one jitted program evaluates strategies
across a *batch of accelerators* (the grid entry points vmap the hardware
axis alongside batch/budget).  Packed workloads carry their pack-time
bytes/elem (``BPE``); evaluation rescales activation/weight bytes to the
serving accelerator's datatype in-graph, which is an exact identity when
the two match.

Array convention (see ``Workload.arrays``): position 0 is the network input
pseudo-tensor, positions ``1..n`` are layers, padded to ``nmax``.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from .accel import AccelConfig, HwVec, as_hw, stack_hw

__all__ = ["SYNC", "CostOut", "evaluate", "evaluate_population",
           "evaluate_population_stats", "baseline_no_fusion", "prefix_trace",
           "pack_workload", "pack_workload_host", "pack_grid_host",
           "stack_workloads", "PrefixConsts", "PrefixCarry",
           "prefix_consts", "prefix_init", "prefix_step", "prefix_out",
           "prefix_probe_peak", "prefix_scan", "evaluate_grid",
           "evaluate_grid_stats", "baseline_grid", "finalize_groups",
           "default_evaluator", "set_default_evaluator"]

SYNC = -1  # strategy sentinel: flush activation off-chip after this layer
_UTIL_MIN = 1.0 / 4096.0

# ---------------------------------------------------------------------------
# Evaluator-backend dispatch (DESIGN.md §13).
#
# The population/grid evaluators have two interchangeable backends: "xla"
# (the vmapped jnp path below) and "pallas" (``kernels.fusion_eval``, the
# block kernel; interpret mode on CPU).  Both share ``finalize_groups`` and
# are bit-identical on the CPU container, so search/teacher pipelines may
# flip backends without changing a single emitted corpus byte.  ``evaluator``
# kwargs accept "xla" | "pallas" | None (None = the module default).
# ---------------------------------------------------------------------------

_EVALUATOR_BACKENDS = ("xla", "pallas")
_DEFAULT_EVALUATOR = "xla"


def default_evaluator() -> str:
    """The backend used when an entry point's ``evaluator=None``."""
    return _DEFAULT_EVALUATOR


def set_default_evaluator(name: str) -> str:
    """Set the process-wide default backend; returns the previous one."""
    global _DEFAULT_EVALUATOR
    prev = _DEFAULT_EVALUATOR
    _DEFAULT_EVALUATOR = _resolve_evaluator(name)
    return prev


def _resolve_evaluator(evaluator: str | None) -> str:
    ev = _DEFAULT_EVALUATOR if evaluator is None else evaluator
    if ev not in _EVALUATOR_BACKENDS:
        raise ValueError(f"evaluator must be one of {_EVALUATOR_BACKENDS}, "
                         f"got {ev!r}")
    return ev


class CostOut(NamedTuple):
    latency: jax.Array      # seconds, end-to-end
    peak_mem: jax.Array     # bytes, max over fused groups
    traffic: jax.Array      # bytes, total off-chip
    valid: jax.Array        # peak_mem <= budget
    n_groups: jax.Array     # number of fused groups


def pack_workload_host(workload, hw: AccelConfig,
                       nmax: int = 64) -> dict[str, np.ndarray]:
    """Packed workload as host numpy arrays (bytes scaled by
    hw.bytes_per_elem): f32 A/W/F/OE/UC/SHAPE6, int32 SKIP and ``n``, bool
    ``mask``.

    ``BPE`` records the pack-time bytes/elem so the evaluators can rescale
    A/W when serving the same packing on an accelerator with a different
    datatype (DESIGN §11) — identity when they match."""
    arrs = workload.arrays(nmax, bytes_per_elem=hw.bytes_per_elem)
    out = {k: np.asarray(v, dtype=np.float32) for k, v in arrs.items()
           if k in ("A", "W", "F", "OE", "UC", "SHAPE6")}
    out["SKIP"] = np.asarray(arrs["SKIP"], dtype=np.int32)
    out["mask"] = np.asarray(arrs["mask"], dtype=bool)
    out["n"] = np.asarray(arrs["n"], dtype=np.int32)
    out["BPE"] = np.asarray(hw.bytes_per_elem, np.float32)
    return out


def pack_workload(workload, hw: AccelConfig, nmax: int = 64) -> dict[str, jnp.ndarray]:
    """Device-ready workload arrays: the transfer of
    :func:`pack_workload_host`'s rows."""
    return {k: jnp.asarray(v)
            for k, v in pack_workload_host(workload, hw, nmax).items()}


def pack_grid_host(workloads: list, hws: list,
                   nmax: int = 64) -> dict[str, np.ndarray]:
    """:func:`stack_workloads` of ``pack_workload(workloads[c], hws[c])``,
    built on the host: one numpy array per leaf, so a jitted grid program
    moves each leaf to the device once, as its argument."""
    rows = [pack_workload_host(w, h, nmax) for w, h in zip(workloads, hws)]
    return {k: np.stack([r[k] for r in rows]) for k in rows[0]}


def _scaled_AW(wl: dict, hw: HwVec) -> tuple[jax.Array, jax.Array]:
    """A/W rescaled from pack-time bytes to ``hw``'s bytes/elem.

    The multiplier is exactly 1.0 when the serving accelerator matches the
    packing (IEEE identity), so the static-hw path stays bit-exact."""
    A, W = wl["A"], wl["W"]
    bpe = wl.get("BPE")
    if bpe is None:
        return A, W
    s = hw.bytes_per_elem / bpe
    return A * s, W * s


def stack_workloads(wls: list[dict]) -> dict[str, jnp.ndarray]:
    """Stack packed workloads (same ``nmax``) along a leading condition axis.

    The stacked dict vmaps through every cost-model entry point — this is
    what lets a heterogeneous (workload, budget) condition grid evaluate in
    one device program (``evaluate_grid``, DESIGN §10) and a mixed-network
    request batch serve in one fused call (``infer.dnnfuser_infer_batch``,
    DESIGN §12).  Entry ``c`` may repeat a workload; rows with different
    true layer counts ride their per-row ``n`` — positions past it are
    masked (padding stays SYNC/zero), so padding to a shared ``nmax``
    never changes a row's cost."""
    sizes = {int(np.shape(w["A"])[-1]) for w in wls}
    if len(sizes) > 1:
        raise ValueError(f"cannot stack workloads packed to different nmax "
                         f"{sorted(sizes)}; repack to a shared bucket")
    keys = wls[0].keys()
    return {k: jnp.stack([w[k] for w in wls]) for k in keys}


def _prep_strategy(strategy: jax.Array, mask: jax.Array, batch: float) -> tuple:
    """Clip/normalize a raw strategy vector.

    Returns (sync, stage_mb, mbe) where ``sync`` marks flush positions,
    ``stage_mb`` is the staged-output micro-batch (1-sample FIFO at syncs)
    and ``mbe`` is the effective compute micro-batch (syncs inherit their
    producer's granularity).
    """
    s = strategy.astype(jnp.float32)
    sync = (s < 0.0) & mask                       # position 0 can never sync
    mb = jnp.clip(s, 1.0, batch)
    prev_mb = jnp.roll(mb, 1).at[0].set(1.0)
    prev_sync = jnp.roll(sync, 1).at[0].set(False)
    mbe = jnp.where(sync, jnp.where(prev_sync, 1.0, prev_mb), mb)
    stage_mb = jnp.where(sync, 1.0, mb)
    return sync, stage_mb, mbe


def _evaluate_full(wl: dict, strategy: jax.Array, batch: jax.Array,
                   budget_bytes: jax.Array, hw,
                   nseg: int | None = None):
    """``evaluate`` body, additionally returning the group decomposition
    (``gid`` [P] and per-group activation memory ``M_g`` [nseg]) that search
    heuristics (G-Sampler repair) use to pick split/shrink targets."""
    hw = as_hw(hw)
    A, W = _scaled_AW(wl, hw)
    F, OE, UC = wl["F"], wl["OE"], wl["UC"]
    mask, skip, n = wl["mask"], wl["SKIP"], wl["n"]
    P = A.shape[0]
    nseg = nseg or P
    pos = jnp.arange(P)
    B = jnp.asarray(batch, jnp.float32)

    sync, stage_mb, mbe = _prep_strategy(strategy, mask, B)
    fmask = mask.astype(jnp.float32)

    # --- group segmentation -------------------------------------------------
    gid = (jnp.cumsum(sync.astype(jnp.int32)) - sync.astype(jnp.int32))
    head = mask & (jnp.roll(sync, 1).at[0].set(False) | (pos == 1))
    tail = mask & (sync | (pos == n))
    glen = jax.ops.segment_sum(fmask, gid, num_segments=nseg,
                               indices_are_sorted=True)
    fused = (glen[gid] > 1.0) & mask
    # an isolated (unfused) layer runs baseline-style: one full-batch pass
    mbe = jnp.where(fused, mbe, B)

    A_prev = jnp.roll(A, 1).at[0].set(0.0)

    # --- skip (residual) edges ----------------------------------------------
    has_skip = (skip >= 0) & mask
    src = jnp.clip(skip, 0, P - 1)
    same_group = has_skip & (gid[src] == gid)
    skip_hold = jnp.where(same_group, mbe * A[src], 0.0)
    skip_traffic = jnp.where(has_skip & ~same_group, 2.0 * B * A[src], 0.0)

    # --- per-group peak (activation) memory ----------------------------------
    # Weights use a separate streaming path (DESIGN §3): the buffer
    # constraint — the paper's reported "Act. Usage" — is on staged acts.
    m_fused = (stage_mb * A + head.astype(jnp.float32) * mbe * A_prev
               + skip_hold)
    mem_i = jnp.where(fused, m_fused, jnp.minimum(m_fused, hw.stream_buf_bytes))
    M_g = jax.ops.segment_sum(mem_i * fmask, gid, num_segments=nseg,
                              indices_are_sorted=True)

    # --- off-chip traffic ---------------------------------------------------
    # Weights are re-fetched once per micro-batch wave (they are not held in
    # the activation buffer); a full-batch pass fetches them exactly once.
    waves = jnp.ceil(B / mbe)
    t_i = (head.astype(jnp.float32) * B * A_prev
           + tail.astype(jnp.float32) * B * A + W * waves + skip_traffic)
    T_g = jax.ops.segment_sum(t_i * fmask, gid, num_segments=nseg,
                              indices_are_sorted=True)

    # --- compute / on-chip / overheads ---------------------------------------
    util = jnp.clip(mbe * OE / (hw.npe * hw.pe_lanes), _UTIL_MIN, UC)
    comp = B * F / hw.peak_macs / util
    C_g = jax.ops.segment_sum(comp * fmask, gid, num_segments=nseg,
                              indices_are_sorted=True)
    o_i = B * (A_prev + A) + W * waves
    O_g = jax.ops.segment_sum(o_i * fmask, gid, num_segments=nseg,
                              indices_are_sorted=True)
    wave_g = jax.ops.segment_sum(waves * fmask, gid, num_segments=nseg,
                                 indices_are_sorted=True)

    out = finalize_groups(C_g, T_g, O_g, M_g, wave_g, glen,
                          budget_bytes, hw)
    return out, gid, M_g


def finalize_groups(C_g, T_g, O_g, M_g, wave_g, glen, budget_bytes,
                    hw) -> CostOut:
    """Per-group decomposition -> CostOut (the shared reduction, DESIGN §13).

    Inputs are the per-group component sums over the trailing group axis —
    compute seconds, off-chip bytes, on-chip bytes, staged-act bytes,
    micro-batch waves and member counts — exactly what the sorted
    segment-sums above and the Pallas ``kernels.fusion_eval`` block kernel
    both accumulate (in the same position order).  BOTH evaluator backends
    funnel through this function, so the roofline max and the latency /
    traffic / peak reductions lower identically — the keystone of the
    backends' bit-exact equivalence.  ``hw`` leaves may carry broadcast
    batch axes ([C, 1, 1] for grid blocks)."""
    hw = as_hw(hw)
    nonempty = glen > 0.0
    peak_mem = jnp.max(jnp.where(nonempty, M_g, 0.0), axis=-1)
    fill_g = wave_g * hw.t_pass + nonempty.astype(jnp.float32) * hw.t_sync
    L_g = jnp.maximum(jnp.maximum(C_g, T_g / hw.bw_offchip),
                      O_g / hw.bw_onchip) + fill_g
    latency = jnp.sum(L_g, axis=-1)
    traffic = jnp.sum(T_g, axis=-1)
    n_groups = jnp.sum(nonempty.astype(jnp.int32), axis=-1)
    valid = peak_mem <= jnp.asarray(budget_bytes, jnp.float32)
    return CostOut(latency, peak_mem, traffic, valid, n_groups)


@functools.partial(jax.jit, static_argnames=("nseg",))
def _evaluate_jit(wl, strategy, batch, budget_bytes, hw, nseg=None):
    out, _, _ = _evaluate_full(wl, strategy, batch, budget_bytes, hw, nseg)
    return out


def evaluate(wl: dict, strategy: jax.Array, batch: jax.Array,
             budget_bytes: jax.Array, hw, *,
             nseg: int | None = None) -> CostOut:
    """Cost of one strategy. All inputs may be traced except ``nseg`` —
    including ``hw`` (AccelConfig or ``accel.HwVec``, DESIGN §11)."""
    return _evaluate_jit(wl, strategy, batch, budget_bytes, as_hw(hw),
                         nseg=nseg)


@jax.jit
def _baseline_jit(wl, batch, hw):
    hw = as_hw(hw)
    A, W = _scaled_AW(wl, hw)
    F, OE, UC = wl["F"], wl["OE"], wl["UC"]
    mask = wl["mask"]
    B = jnp.asarray(batch, jnp.float32)
    fmask = mask.astype(jnp.float32)
    A_prev = jnp.roll(A, 1).at[0].set(0.0)
    util = jnp.clip(B * OE / (hw.npe * hw.pe_lanes), _UTIL_MIN, UC)
    comp = B * F / hw.peak_macs / util
    t_i = B * (A_prev + A) + W
    o_i = t_i
    L_i = jnp.maximum(jnp.maximum(comp, t_i / hw.bw_offchip),
                      o_i / hw.bw_onchip) + hw.t_sync
    latency = jnp.sum(L_i * fmask)
    traffic = jnp.sum(t_i * fmask)
    peak = jnp.asarray(hw.stream_buf_bytes, jnp.float32)
    n = jnp.sum(mask.astype(jnp.int32))
    return CostOut(latency, peak, traffic, jnp.asarray(True), n)


def baseline_no_fusion(wl: dict, batch: jax.Array, hw) -> CostOut:
    """The paper's baseline: best layer-by-layer mapping, full batch per
    layer, minimal buffer, every activation round-trips off-chip."""
    return _baseline_jit(wl, batch, as_hw(hw))


@jax.jit
def _population_jit(wl, strategies, batch, budget_bytes, hw):
    return jax.vmap(
        lambda s: _evaluate_jit(wl, s, batch, budget_bytes, hw))(strategies)


def evaluate_population(wl: dict, strategies: jax.Array, batch: jax.Array,
                        budget_bytes: jax.Array, hw, *,
                        evaluator: str | None = None) -> CostOut:
    """Vectorized cost of a population ``[pop, P]`` of strategies.

    ``evaluator`` selects the backend ("xla" | "pallas" | None = the
    module default, DESIGN §13); both are bit-identical on CPU."""
    if _resolve_evaluator(evaluator) == "pallas":
        from ..kernels.fusion_eval import fusion_eval_population
        return fusion_eval_population(strategies, wl, batch=batch,
                                      budget_bytes=budget_bytes, hw=hw)
    return _population_jit(wl, strategies, batch, budget_bytes, as_hw(hw))


@jax.jit
def _population_stats_jit(wl, strategies, batch, budget_bytes, hw):
    return jax.vmap(
        lambda s: _evaluate_full(wl, s, batch, budget_bytes, hw))(strategies)


def evaluate_population_stats(wl: dict, strategies: jax.Array,
                              batch: jax.Array, budget_bytes: jax.Array,
                              hw, *, evaluator: str | None = None):
    """Like :func:`evaluate_population` but also returns the per-strategy
    group decomposition: ``(CostOut [pop], gid [pop, P], M_g [pop, P])``.

    ``gid[p, i]`` is the fused-group id of position ``i`` in strategy ``p``
    and ``M_g[p, g]`` that group's staged-activation peak — everything a
    constraint-repair operator needs to find the worst group and its span
    in one device call (DESIGN.md §3)."""
    if _resolve_evaluator(evaluator) == "pallas":
        from ..kernels.fusion_eval import fusion_eval_population_stats
        return fusion_eval_population_stats(strategies, wl, batch=batch,
                                            budget_bytes=budget_bytes, hw=hw)
    return _population_stats_jit(wl, strategies, batch, budget_bytes,
                                 as_hw(hw))


# ---------------------------------------------------------------------------
# Condition-grid evaluation (DESIGN.md §10, §11).
#
# A teacher run sweeps a grid of C = |workloads| x |accels| x |budgets|
# conditions, each with its own GA population.  The three entry points below
# vmap the per-condition evaluators over a ``stack_workloads`` dict plus
# per-condition batch/budget vectors AND a per-condition ``accel.stack_hw``
# hardware vector, so a whole grid generation — C x POP strategies across
# heterogeneous accelerators — costs one device call (and, inside the fused
# GA, zero host round trips).
# ---------------------------------------------------------------------------


@jax.jit
def _grid_jit(wls, strategies, batches, budgets, hw):
    return jax.vmap(
        lambda wl, s, b, m, h: _population_jit(wl, s, b, m, h)
    )(wls, strategies, batches, budgets, hw)


def evaluate_grid(wls: dict, strategies: jax.Array, batches: jax.Array,
                  budgets: jax.Array, hw, *,
                  evaluator: str | None = None) -> CostOut:
    """CostOut [C, POP] of per-condition populations ``strategies``
    [C, POP, P] over stacked workloads [C, ...], per-condition ``batches``
    / ``budgets`` [C] and per-condition hardware (anything
    ``accel.stack_hw`` accepts: one config, a list, or stacked vectors).

    ``evaluator`` selects the backend (DESIGN §13): "xla" vmaps the jnp
    evaluator, "pallas" runs the ``kernels.fusion_eval`` block kernel
    (interpret mode on CPU) — bit-identical outputs either way.  Either
    runs under the name scope ``evaluate_grid``."""
    with jax.named_scope("evaluate_grid"):
        if _resolve_evaluator(evaluator) == "pallas":
            from ..kernels.fusion_eval import fusion_eval_grid
            return fusion_eval_grid(wls, strategies, batches, budgets, hw)
        return _grid_jit(wls, strategies, batches, budgets,
                         stack_hw(hw, strategies.shape[0]))


@jax.jit
def _grid_stats_jit(wls, strategies, batches, budgets, hw):
    return jax.vmap(
        lambda wl, s, b, m, h: jax.vmap(
            lambda one: _evaluate_full(wl, one, b, m, h))(s)
    )(wls, strategies, batches, budgets, hw)


def evaluate_grid_stats(wls: dict, strategies: jax.Array, batches: jax.Array,
                        budgets: jax.Array, hw, *,
                        evaluator: str | None = None):
    """Grid counterpart of :func:`evaluate_population_stats`:
    ``(CostOut [C, POP], gid [C, POP, P], M_g [C, POP, P])`` — the
    constraint-repair operator's split/shrink targets for every child of
    every condition in one call.  ``evaluator`` and the name scope as in
    :func:`evaluate_grid` (DESIGN §13)."""
    with jax.named_scope("evaluate_grid"):
        if _resolve_evaluator(evaluator) == "pallas":
            from ..kernels.fusion_eval import fusion_eval_grid_stats
            return fusion_eval_grid_stats(wls, strategies, batches, budgets,
                                          hw)
        return _grid_stats_jit(wls, strategies, batches, budgets,
                               stack_hw(hw, strategies.shape[0]))


@jax.jit
def _baseline_grid_jit(wls, batches, hw):
    return jax.vmap(lambda wl, b, h: _baseline_jit(wl, b, h)
                    )(wls, batches, hw)


def baseline_grid(wls: dict, batches: jax.Array, hw) -> CostOut:
    """Per-condition no-fusion baselines, CostOut [C]."""
    return _baseline_grid_jit(wls, batches,
                              stack_hw(hw, np.shape(batches)[0]))


@jax.jit
def _prefix_trace_jit(wl, strategy, batch, budget_bytes, hw):
    P = strategy.shape[0]
    pos = jnp.arange(P)

    def at_t(t):
        s = jnp.where(pos < t, strategy, SYNC)
        return _evaluate_jit(wl, s, batch, budget_bytes, hw)

    return jax.vmap(at_t)(jnp.arange(P))


def prefix_trace(wl: dict, strategy: jax.Array, batch: jax.Array,
                 budget_bytes: jax.Array, hw) -> CostOut:
    """Partial-strategy trace for RL state decoration (paper Eq. 2).

    Entry ``t`` evaluates the strategy with only positions ``< t`` applied
    (the rest forced to sync) — i.e. the environment state *before* action
    ``t``: ``P_{a_0..a_{t-1}}`` and the memory committed so far.
    Returns CostOut with a leading axis of length ``P``.
    """
    return _prefix_trace_jit(wl, strategy, batch, budget_bytes, as_hw(hw))


# ---------------------------------------------------------------------------
# Incremental prefix evaluation (scan-carry form, DESIGN.md §9).
#
# ``prefix_trace`` above re-evaluates the whole chain once per position —
# O(P^2) work for a rollout that queries the environment at every step.  The
# carry form below maintains the exact same quantity — the cost of the
# strategy with positions ``< t`` applied and the rest forced to SYNC —
# as O(1)-per-step running state, so a full autoregressive episode is O(P)
# and lives inside one ``jax.lax.scan`` with zero host syncs.
#
# Invariant: positions ``>= t`` forced to SYNC are each a singleton
# (unfused) group whose cost is independent of the prefix, so their
# latency/traffic suffix-sums and memory suffix-max are precomputed once
# (``PrefixConsts``).  The carry tracks the closed-group aggregates plus the
# component sums of the one open (not-yet-synced) group.
# ---------------------------------------------------------------------------


class PrefixConsts(NamedTuple):
    """Per-(workload, batch, budget, hw) constants for the prefix carry.

    All fields are jnp arrays (``batch``/``budget`` — and since §11 the
    accelerator itself — may be traced, e.g. under a vmap over serving
    conditions); ``A``/``W`` are already rescaled to the accelerator's
    bytes/elem."""
    A: jax.Array          # [P] act bytes/sample (position 0 = network input)
    A_prev: jax.Array     # [P] producer act bytes
    W: jax.Array          # [P] weight bytes
    F: jax.Array          # [P] MACs/sample
    OE: jax.Array         # [P] output elems (utilization model)
    UC: jax.Array         # [P] utilization cap
    skip: jax.Array       # [P] residual source position or -1
    has_skip: jax.Array   # [P] bool
    mask: jax.Array       # [P] valid layer positions
    n: jax.Array          # num layers
    B: jax.Array          # batch (f32)
    budget: jax.Array     # bytes (f32)
    sm: jax.Array         # [P] singleton(all-SYNC) group peak mem
    st: jax.Array         # [P] singleton group off-chip traffic
    slat: jax.Array       # [P] singleton group latency
    hold0: jax.Array      # [P] same-group skip-hold term of a singleton
    SLAT: jax.Array       # [P+2] suffix sum of slat (SLAT[i] = sum_{j>=i})
    SPEAK: jax.Array      # [P+2] suffix max of sm
    STRAF: jax.Array      # [P+2] suffix sum of st
    SGRP: jax.Array       # [P+2] suffix count of layers (i32)


class PrefixCarry(NamedTuple):
    """Running state after committing actions for positions ``< t``."""
    t: jax.Array          # next position to act on (i32)
    g_start: jax.Array    # first position of the open group (i32)
    open_len: jax.Array   # committed members of the open group (i32)
    last_mb: jax.Array    # micro-batch of the last committed member (f32)
    c_sum: jax.Array      # open-group compute seconds
    t_sum: jax.Array      # open-group off-chip bytes
    o_sum: jax.Array      # open-group on-chip bytes
    m_sum: jax.Array      # open-group staged-act bytes
    w_sum: jax.Array      # open-group micro-batch waves
    lat: jax.Array        # closed groups: total latency
    peak: jax.Array       # closed groups: max group memory
    traf: jax.Array       # closed groups: total traffic
    groups: jax.Array     # closed groups: count (i32)


def _suffix_sum(x: jax.Array, pad: int = 2) -> jax.Array:
    s = jnp.cumsum(x[::-1])[::-1]
    return jnp.concatenate([s, jnp.zeros((pad,), x.dtype)])


def _suffix_max(x: jax.Array, pad: int = 2) -> jax.Array:
    s = jax.lax.cummax(x[::-1])[::-1]
    return jnp.concatenate([s, jnp.zeros((pad,), x.dtype)])


def prefix_consts(wl: dict, batch: jax.Array, budget_bytes: jax.Array,
                  hw) -> PrefixConsts:
    """Precompute the per-position constants of the forced-SYNC suffix.

    A forced-SYNC position is a singleton group: unfused, so its effective
    micro-batch is the full batch, its staged output one sample, and its
    working set clamped to the streaming buffer — none of which depends on
    the actions taken for the prefix (see ``evaluate``)."""
    hw = as_hw(hw)
    A, W = _scaled_AW(wl, hw)
    F = wl["F"]
    OE, UC = wl["OE"], wl["UC"]
    mask, skip, n = wl["mask"], wl["SKIP"], wl["n"]
    P = A.shape[0]
    pos = jnp.arange(P)
    B = jnp.asarray(batch, jnp.float32)
    fmask = mask.astype(jnp.float32)
    A_prev = jnp.roll(A, 1).at[0].set(0.0)
    src = jnp.clip(skip, 0, P - 1)
    has = (skip >= 0) & mask
    Asrc = A[src]
    # position 0 shares gid 0 with the first group, so a residual edge from
    # the network input into position 1 is same-group even for a singleton
    same0 = has & (skip == 0) & (pos == 1)
    hold0 = jnp.where(same0, B * Asrc, 0.0)
    cross = jnp.where(has & ~same0, 2.0 * B * Asrc, 0.0)
    util_B = jnp.clip(B * OE / (hw.npe * hw.pe_lanes), _UTIL_MIN, UC)
    comp_B = B * F / hw.peak_macs / util_B
    sm = jnp.minimum(A + B * A_prev + hold0, hw.stream_buf_bytes) * fmask
    st = (B * A_prev + B * A + W + cross) * fmask
    so = B * (A_prev + A) + W
    slat = (jnp.maximum(jnp.maximum(comp_B, st / hw.bw_offchip),
                        so / hw.bw_onchip) + hw.t_pass + hw.t_sync) * fmask
    return PrefixConsts(
        A=A, A_prev=A_prev, W=W, F=F, OE=OE, UC=UC,
        skip=skip, has_skip=has, mask=mask, n=n, B=B,
        budget=jnp.asarray(budget_bytes, jnp.float32),
        sm=sm, st=st, slat=slat, hold0=hold0,
        SLAT=_suffix_sum(slat), SPEAK=_suffix_max(sm),
        STRAF=_suffix_sum(st),
        SGRP=_suffix_sum(fmask).astype(jnp.int32))


def prefix_init(consts: PrefixConsts) -> PrefixCarry:
    f0 = jnp.float32(0.0)
    i0 = jnp.int32(0)
    return PrefixCarry(t=i0, g_start=jnp.int32(1), open_len=i0,
                       last_mb=jnp.float32(1.0), c_sum=f0, t_sum=f0,
                       o_sum=f0, m_sum=f0, w_sum=f0, lat=f0, peak=f0,
                       traf=f0, groups=i0)


def _tree_select(pred, a, b):
    return jax.tree_util.tree_map(lambda x, y: jnp.where(pred, x, y), a, b)


def _gather(consts: PrefixConsts, i: jax.Array):
    """Per-position terms at (clipped) position ``i``."""
    P = consts.A.shape[0]
    j = jnp.clip(i, 0, P - 1)
    return (consts.A[j], consts.A_prev[j], consts.W[j], consts.F[j],
            consts.OE[j], consts.UC[j], consts.skip[j], consts.has_skip[j])


def _same_group(consts: PrefixConsts, src, has, g_start):
    """gid[src] == gid[i] for ``i`` in the open group starting at g_start
    (position 0 always carries gid 0, the id of the first group)."""
    return has & ((src >= g_start) | ((src == 0) & (g_start == 1)))


def prefix_step(consts: PrefixConsts, carry: PrefixCarry, action,
                hw) -> PrefixCarry:
    """Commit ``action`` for position ``carry.t`` (O(1) work).

    Matches ``evaluate`` semantics exactly: a non-SYNC action extends the
    open group (fused-style terms); a SYNC action closes it — as a
    precomputed singleton when the group would hold one sync'd position, or
    by reducing the carried component sums.  Position 0 is the network-input
    pseudo tensor and contributes nothing."""
    hw = as_hw(hw)
    c = consts
    i = carry.t
    B = c.B
    lanes = hw.npe * hw.pe_lanes
    a = jnp.asarray(action, jnp.float32)
    Ai, Api, Wi, Fi, OEi, UCi, srci, hasi = _gather(c, i)
    Asrc = c.A[jnp.clip(srci, 0, c.A.shape[0] - 1)]
    same = _same_group(c, srci, hasi, carry.g_start)
    is_tail_n = i == c.n

    # --- non-SYNC: extend the open group (fused-style contributions) -------
    mb = jnp.clip(a, 1.0, B)
    head = carry.open_len == 0
    waves = jnp.ceil(B / mb)
    util = jnp.clip(mb * OEi / lanes, _UTIL_MIN, UCi)
    comp = B * Fi / hw.peak_macs / util
    mem = (mb * Ai + jnp.where(head, mb * Api, 0.0)
           + jnp.where(same, mb * Asrc, 0.0))
    tr = (jnp.where(head, B * Api, 0.0) + jnp.where(is_tail_n, B * Ai, 0.0)
          + Wi * waves + jnp.where(hasi & ~same, 2.0 * B * Asrc, 0.0))
    o = B * (Api + Ai) + Wi * waves
    carry_ns = carry._replace(
        t=i + 1, open_len=carry.open_len + 1, last_mb=mb,
        c_sum=carry.c_sum + comp, t_sum=carry.t_sum + tr,
        o_sum=carry.o_sum + o, m_sum=carry.m_sum + mem,
        w_sum=carry.w_sum + waves)

    # --- SYNC: close the open group ----------------------------------------
    # fused close: the sync position rides the producer's micro-batch with a
    # 1-sample staged FIFO; singleton close: the precomputed all-SYNC terms.
    mbe = carry.last_mb
    waves_s = jnp.ceil(B / mbe)
    util_s = jnp.clip(mbe * OEi / lanes, _UTIL_MIN, UCi)
    comp_s = B * Fi / hw.peak_macs / util_s
    mem_s = Ai + jnp.where(same, mbe * Asrc, 0.0)
    tr_s = (B * Ai + Wi * waves_s
            + jnp.where(hasi & ~same, 2.0 * B * Asrc, 0.0))
    o_s = B * (Api + Ai) + Wi * waves_s
    Mg = carry.m_sum + mem_s
    Cg = carry.c_sum + comp_s
    Tg = carry.t_sum + tr_s
    Og = carry.o_sum + o_s
    Wg = carry.w_sum + waves_s
    Lg = (jnp.maximum(jnp.maximum(Cg, Tg / hw.bw_offchip),
                      Og / hw.bw_onchip) + Wg * hw.t_pass + hw.t_sync)
    single = carry.open_len == 0
    j = jnp.clip(i, 0, c.A.shape[0] - 1)
    Lc = jnp.where(single, c.slat[j], Lg)
    Mc = jnp.where(single, c.sm[j], Mg)
    Tc = jnp.where(single, c.st[j], Tg)
    f0 = jnp.float32(0.0)
    carry_sy = PrefixCarry(
        t=i + 1, g_start=i + 1, open_len=jnp.int32(0),
        last_mb=jnp.float32(1.0), c_sum=f0, t_sum=f0, o_sum=f0, m_sum=f0,
        w_sum=f0, lat=carry.lat + Lc, peak=jnp.maximum(carry.peak, Mc),
        traf=carry.traf + Tc, groups=carry.groups + 1)

    out = _tree_select(a < 0.0, carry_sy, carry_ns)
    return _tree_select(i == 0, carry._replace(t=jnp.int32(1)), out)


def prefix_out(consts: PrefixConsts, carry: PrefixCarry,
               hw) -> CostOut:
    """CostOut of the carried prefix: actions ``< t`` applied, rest SYNC.

    Identical quantity to ``prefix_trace`` entry ``t`` (and to a full
    ``evaluate`` once ``t == n + 1``), assembled in O(1) from the carry,
    one forced-SYNC close of the open group, and the precomputed suffix
    aggregates."""
    hw = as_hw(hw)
    c = consts
    t = carry.t
    B = c.B
    lanes = hw.npe * hw.pe_lanes
    n1 = c.n + 1
    tc = jnp.clip(t, 0, c.SLAT.shape[0] - 2)

    # case A — no open group: closed + all-SYNC suffix from t
    latA = carry.lat + c.SLAT[tc]
    peakA = jnp.maximum(carry.peak, c.SPEAK[tc])
    trafA = carry.traf + c.STRAF[tc]
    grpA = carry.groups + c.SGRP[tc]

    # case B — open group force-closed by the SYNC at t, suffix from t+1
    Ai, Api, Wi, Fi, OEi, UCi, srci, hasi = _gather(c, t)
    Asrc = c.A[jnp.clip(srci, 0, c.A.shape[0] - 1)]
    same = _same_group(c, srci, hasi, carry.g_start)
    mbe = carry.last_mb
    waves_t = jnp.ceil(B / mbe)
    util_t = jnp.clip(mbe * OEi / lanes, _UTIL_MIN, UCi)
    comp_t = B * Fi / hw.peak_macs / util_t
    mem_t = Ai + jnp.where(same, mbe * Asrc, 0.0)
    tr_t = (B * Ai + Wi * waves_t
            + jnp.where(hasi & ~same, 2.0 * B * Asrc, 0.0))
    o_t = B * (Api + Ai) + Wi * waves_t
    Mg = carry.m_sum + mem_t
    Cg = carry.c_sum + comp_t
    Tg = carry.t_sum + tr_t
    Og = carry.o_sum + o_t
    Wg = carry.w_sum + waves_t
    Lg = (jnp.maximum(jnp.maximum(Cg, Tg / hw.bw_offchip),
                      Og / hw.bw_onchip) + Wg * hw.t_pass + hw.t_sync)
    latB = carry.lat + Lg + c.SLAT[tc + 1]
    peakB = jnp.maximum(jnp.maximum(carry.peak, Mg), c.SPEAK[tc + 1])
    trafB = carry.traf + Tg + c.STRAF[tc + 1]
    grpB = carry.groups + 1 + c.SGRP[tc + 1]

    # case C — t == n+1, the episode is complete: close the open group
    # as-is.  A 1-member group is unfused and re-derived from the singleton
    # constants (full-batch pass, staged output at its own micro-batch,
    # streaming-buffer clamp); >= 2 members close from the carried sums.
    jn = jnp.clip(c.n, 0, c.A.shape[0] - 1)
    memC1 = jnp.minimum(
        carry.last_mb * c.A[jn] + B * c.A_prev[jn] + c.hold0[jn],
        hw.stream_buf_bytes)
    latC1 = carry.lat + c.slat[jn]
    peakC1 = jnp.maximum(carry.peak, memC1)
    trafC1 = carry.traf + c.st[jn]
    LgC = (jnp.maximum(jnp.maximum(carry.c_sum,
                                   carry.t_sum / hw.bw_offchip),
                       carry.o_sum / hw.bw_onchip)
           + carry.w_sum * hw.t_pass + hw.t_sync)
    latC2 = carry.lat + LgC
    peakC2 = jnp.maximum(carry.peak, carry.m_sum)
    trafC2 = carry.traf + carry.t_sum

    open0 = carry.open_len == 0
    open1 = carry.open_len == 1
    latC = jnp.where(open0, carry.lat, jnp.where(open1, latC1, latC2))
    peakC = jnp.where(open0, carry.peak, jnp.where(open1, peakC1, peakC2))
    trafC = jnp.where(open0, carry.traf, jnp.where(open1, trafC1, trafC2))
    grpC = carry.groups + jnp.where(open0, 0, 1)

    done = t >= n1
    lat = jnp.where(done, latC, jnp.where(open0, latA, latB))
    peak = jnp.where(done, peakC, jnp.where(open0, peakA, peakB))
    traf = jnp.where(done, trafC, jnp.where(open0, trafA, trafB))
    grp = jnp.where(done, grpC, jnp.where(open0, grpA, grpB))
    return CostOut(lat, peak, traf, peak <= c.budget, grp)


def prefix_probe_peak(consts: PrefixConsts, carry: PrefixCarry, action,
                      hw) -> jax.Array:
    """Peak memory of the probe strategy (``action`` at position ``t``,
    everything after forced SYNC) — the quantity the inference-time budget
    guard tests, without the latency/roofline math of a full
    ``prefix_step`` + ``prefix_out`` round trip.

    Equals ``prefix_out(prefix_step(carry, action)).peak_mem`` for a
    non-SYNC ``action`` (the guard never probes SYNC)."""
    hw = as_hw(hw)
    c = consts
    i = carry.t
    B = c.B
    mb = jnp.clip(jnp.asarray(action, jnp.float32), 1.0, B)
    Ai, Api, _, _, _, _, srci, hasi = _gather(c, i)
    Asrc = c.A[jnp.clip(srci, 0, c.A.shape[0] - 1)]
    same = _same_group(c, srci, hasi, carry.g_start)
    head = carry.open_len == 0
    mem_t = (mb * Ai + jnp.where(head, mb * Api, 0.0)
             + jnp.where(same, mb * Asrc, 0.0))
    tc = jnp.clip(i + 1, 0, c.A.shape[0] - 1)
    A1, src1, has1 = c.A[tc], c.skip[tc], c.has_skip[tc]
    same1 = _same_group(c, src1, has1, carry.g_start)
    mem_s = A1 + jnp.where(same1, mb * c.A[jnp.clip(src1, 0,
                                                    c.A.shape[0] - 1)], 0.0)
    # t < n: fused group [g_start..t+1] + all-SYNC suffix from t+2
    sfx = jnp.clip(i + 2, 0, c.SLAT.shape[0] - 1)
    peak_mid = jnp.maximum(carry.m_sum + mem_t + mem_s, c.SPEAK[sfx])
    # t == n: the strategy is complete after this action
    jn = jnp.clip(c.n, 0, c.A.shape[0] - 1)
    mem_single = jnp.minimum(mb * c.A[jn] + B * c.A_prev[jn] + c.hold0[jn],
                             hw.stream_buf_bytes)
    peak_end = jnp.where(head, mem_single, carry.m_sum + mem_t)
    grp = jnp.where(i >= c.n, peak_end, peak_mid)
    # t > n: inactive lane — nothing left to commit
    grp = jnp.where(i > c.n, jnp.float32(0.0), grp)
    # t == 0: the input pseudo-tensor carries no cost; all-SYNC chain
    grp = jnp.where(i == 0, c.SPEAK[1], grp)
    return jnp.maximum(carry.peak, grp)


@jax.jit
def _prefix_scan_jit(wl, strategy, batch, budget_bytes, hw):
    consts = prefix_consts(wl, batch, budget_bytes, hw)
    carry = prefix_init(consts)

    def step(carry, a):
        out = prefix_out(consts, carry, hw)
        new = prefix_step(consts, carry, a, hw)
        carry = _tree_select(carry.t <= consts.n, new, carry)
        return carry, out

    carry, trace = jax.lax.scan(step, carry, strategy)
    return trace, prefix_out(consts, carry, hw)


def prefix_scan(wl: dict, strategy: jax.Array, batch: jax.Array,
                budget_bytes: jax.Array, hw):
    """Carry-based equivalent of :func:`prefix_trace`.

    Returns ``(trace, final)``: ``trace`` is a CostOut with leading axis
    ``P`` whose entry ``t`` matches ``prefix_trace`` entry ``t``, and
    ``final`` the full-strategy CostOut — all from one O(P) scan instead of
    P full evaluations."""
    return _prefix_scan_jit(wl, strategy, batch, budget_bytes, as_hw(hw))


def random_strategy(rng: np.random.Generator, n: int, nmax: int, batch: int,
                    p_sync: float = 0.3) -> np.ndarray:
    """A random valid-format strategy (numpy; for tests and search seeds)."""
    s = np.full(nmax, SYNC, dtype=np.int32)
    vals = rng.integers(1, batch + 1, size=n + 1)
    syncs = rng.random(n + 1) < p_sync
    syncs[0] = False
    s[: n + 1] = np.where(syncs, SYNC, vals)
    return s
