"""Population fusion-strategy evaluation (TPU Pallas) — the paper's search
hot loop as a kernel, in production grid form (DESIGN §7, §13).

G-Sampler evaluates thousands of strategies per generation across a whole
(workload x accelerator x budget) condition grid; this kernel evaluates a
BLOCK of ``bp`` candidate strategies per grid step, laid out ``[P, bp]``
with the candidates on the vector lanes.  The per-condition layer table
(A/W/F/OE/UC/SKIP, padded to P positions), ``n`` and the [batch, BPE, hw]
row live in SMEM, one condition per grid row: every per-position term is
a scalar broadcast across the lanes, and the residual-source read is a
dynamic scalar load.  Per-candidate group accumulators are [1, bp] vectors
carried through a ``fori_loop`` over the P chain positions, and closed
groups are written into the per-group output blocks in place — no HBM
traffic beyond one read of the strategy block and one write of the
per-group result matrices.

THE ACCELERATOR IS TRACED DATA, not a compile-time constant: the hardware
descriptor enters as a per-condition ``[C, HW_FEATURE_DIM]`` row (any form
``accel.stack_hw`` accepts) and the pack-time ``wl["BPE"]`` -> serving
``bytes_per_elem`` A/W rescale happens IN-KERNEL — exactly the
``cost_model._scaled_AW`` contract of DESIGN §11, an IEEE identity when the
datatypes match.  Sweeping the whole ``ACCEL_ZOO`` therefore reuses ONE
compiled program per block shape (zero recompiles across accelerators).

BIT-EXACTNESS CONTRACT (DESIGN §13): the kernel emits the per-group
decomposition (compute / traffic / on-chip / memory / waves / length
vectors plus per-position group ids) accumulated in the same position order
as ``cost_model._evaluate_full``'s sorted segment-sums, and the CostOut
roofline/reduction step runs OUTSIDE the kernel through
``cost_model.finalize_groups`` — the same jnp expressions the XLA evaluator
lowers.  On the CPU (interpret mode, chosen from the platform) the two
backends are bit-identical, which is what lets
``gsampler_search_grid`` produce the same teacher corpus on either
``evaluator`` backend.  The oracles are ``kernels/ref.fusion_eval_ref`` /
``fusion_eval_grid_ref`` and the loop-based ``core.ref_model``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..core import cost_model as _cm
from ..core.accel import HW_FEATURE_DIM, hw_array, stack_hw

__all__ = ["fusion_eval_population", "fusion_eval_population_stats",
           "fusion_eval_grid", "fusion_eval_grid_stats"]

_UTIL_MIN = 1.0 / 4096.0
# HW_FIELDS slots the kernel reads from the hw part of its condition row
_NPE, _LANES, _FREQ = 0, 1, 2
_BPE_SLOT, _STREAM = 6, 9
# SMEM condition row: [batch, pack-time BPE, hw...]
_COND_BATCH, _COND_BPE, _COND_HW = 0, 1, 2
# SMEM layer table rows
_TAB_ROWS = ("A", "W", "F", "OE", "UC")
_A, _W, _F, _OE, _UC = range(len(_TAB_ROWS))


def _fe_kernel(tab_ref, skip_ref, n_ref, cond_ref, strat_ref,
               Cg_ref, Tg_ref, Og_ref, Mg_ref, Wg_ref, glen_ref, gid_ref,
               *, P: int):
    """One [P, bp] strategy block of one condition row (lanes = candidates).

    The condition's layer table, skip sources, ``n`` and its [batch, BPE,
    hw...] row are per-condition scalars in SMEM: every per-position term
    is a scalar load broadcast across the candidate lanes, and the
    residual-source read ``A[src]`` is a dynamic scalar load.

    Emits the group decomposition (per-group component sums indexed by
    group id, plus per-position group ids); latency/peak/validity are
    assembled outside by ``cost_model.finalize_groups`` so both evaluator
    backends share one reduction lowering (DESIGN §13)."""
    bp = strat_ref.shape[2]
    n = n_ref[0, 0, 0]
    cond = lambda k: cond_ref[0, 0, k]
    B = cond(_COND_BATCH)
    hw = lambda k: cond(_COND_HW + k)
    lanes = hw(_NPE) * hw(_LANES)
    peak_macs = lanes * hw(_FREQ)
    stream_buf = hw(_STREAM)

    # pack-time -> serving-datatype rescale, in-kernel (DESIGN §11/§13);
    # the multiplier is exactly 1.0 when the datatypes match
    scale = hw(_BPE_SLOT) / cond(_COND_BPE)
    row = lambda r, i: tab_ref[0, r, i]
    A = lambda i: row(_A, i) * scale
    W = lambda i: row(_W, i) * scale

    def util(mbe, oe, uc):
        return jnp.clip(mbe * oe / lanes, _UTIL_MIN, uc)

    zeros = jnp.zeros((1, bp), jnp.float32)
    gpos = jax.lax.broadcasted_iota(jnp.int32, (P, bp), 0)
    # per-group output matrices (group id -> component sums), accumulated
    # in place in their output blocks
    group_refs = (Cg_ref, Tg_ref, Og_ref, Mg_ref, Wg_ref, glen_ref)
    for ref in group_refs:
        ref[0] = jnp.zeros((P, bp), jnp.float32)
    gid_ref[0, 0:1, :] = jnp.zeros((1, bp), jnp.int32)   # position 0: gid 0

    def position(i, carry):
        # open-group accumulators + strategy-prefix carry
        # (the previous position's sync flag rides as int32: Mosaic keeps
        # no i1 vector in a loop carry)
        (g_comp, g_traf, g_on, g_mem, g_wav, g_len,
         scount, prev_sync, prev_mb, lastb) = carry
        prev_sync = prev_sync > 0
        a = strat_ref[0, pl.ds(i, 1), :].astype(jnp.float32)
        live = i <= n                                     # mask: 1 <= i <= n
        Ai, Ap, Wi, Fi = A(i), A(i - 1), W(i), row(_F, i)
        OEi, UCi = row(_OE, i), row(_UC, i)
        src = skip_ref[0, 0, i]
        gid_ref[0, pl.ds(i, 1), :] = scount
        sync = (a < 0.0) & live
        mb = jnp.clip(a, 1.0, B)
        mbe = jnp.where(sync, jnp.where(prev_sync, 1.0, prev_mb), mb)
        stage = jnp.where(sync, 1.0, mb)
        head = g_len == 0.0

        # residual edge: same-group iff the source is after the last sync
        # (gid[src] == gid[i]; position 0 shares gid 0 with the first group)
        has_skip = src >= 0
        same = has_skip & (src.astype(jnp.float32) > lastb)
        Asrc = A(jnp.maximum(src, 0))
        hold = jnp.where(same, mbe * Asrc, 0.0)
        cross_t = jnp.where(has_skip & ~same, 2.0 * B * Asrc, 0.0)

        is_tail = (sync | (i == n)) & live
        waves = jnp.ceil(B / mbe)
        head_f = jnp.where(head, 1.0, 0.0)
        tail_f = jnp.where(is_tail, 1.0, 0.0)
        # fused-style per-position terms — expression order mirrors
        # cost_model._evaluate_full term by term (bit-exactness contract)
        mem_i = stage * Ai + (head_f * mbe) * Ap + hold
        traf_i = (head_f * B) * Ap + (tail_f * B) * Ai + Wi * waves + cross_t
        comp_i = B * Fi / peak_macs / util(mbe, OEi, UCi)
        on_i = B * (Ap + Ai) + Wi * waves

        # streaming alternative: this layer alone in its group (unfused:
        # one full-batch pass, working set clamped to the streaming buffer)
        hold_a = jnp.where(same, B * Asrc, 0.0)
        mem_a = jnp.minimum(stage * Ai + (head_f * B) * Ap + hold_a,
                            stream_buf)
        comp_a = B * Fi / peak_macs / util(jnp.full((1, bp), B), OEi, UCi)
        traf_a = (head_f * B) * Ap + (tail_f * B) * Ai + Wi * 1.0 + cross_t
        on_a = B * (Ap + Ai) + Wi * 1.0

        lv = jnp.where(live, 1.0, 0.0)
        g_comp = g_comp + comp_i * lv
        g_traf = g_traf + traf_i * lv
        g_on = g_on + on_i * lv
        g_mem = g_mem + mem_i * lv
        g_wav = g_wav + waves * lv
        g_len = g_len + lv

        single = g_len == 1.0
        closed = (jnp.where(single, comp_a, g_comp),
                  jnp.where(single, traf_a, g_traf),
                  jnp.where(single, on_a, g_on),
                  jnp.where(single, mem_a, g_mem),
                  jnp.where(single, 1.0, g_wav),
                  g_len)
        onehot = (gpos == scount) & is_tail
        for ref, v in zip(group_refs, closed):
            ref[0] = jnp.where(onehot, v, ref[0])

        rz = lambda x: jnp.where(is_tail, zeros, x)
        sync_i = jnp.where(sync, 1, 0)
        return (rz(g_comp), rz(g_traf), rz(g_on), rz(g_mem), rz(g_wav),
                rz(g_len), scount + sync_i, sync_i, mb,
                jnp.where(sync, i.astype(jnp.float32), lastb))

    prev_mb = jnp.clip(strat_ref[0, 0:1, :].astype(jnp.float32), 1.0, B)
    jax.lax.fori_loop(
        1, P, position,
        (zeros,) * 6 + (jnp.zeros((1, bp), jnp.int32),    # syncs before i
                        jnp.zeros((1, bp), jnp.int32), prev_mb,
                        jnp.full((1, bp), -1.0, jnp.float32)))  # last sync


def _ceil_to(x: int, m: int) -> int:
    return -(-x // m) * m


@functools.partial(jax.jit, static_argnames=("bp", "interpret"))
def _fusion_eval_grid_jit(strategies, wls: dict, batches, budgets, hwrows,
                          *, bp: int, interpret: bool):
    C, POP, P = strategies.shape
    NP = _ceil_to(POP, bp)
    if NP != POP:
        strategies = jnp.pad(strategies, ((0, 0), (0, NP - POP), (0, 0)),
                             constant_values=_cm.SYNC)
    # candidates on lanes: [C, P, NP] blocks of (1, P, bp)
    strat_t = jnp.swapaxes(strategies, 1, 2)
    tab = jnp.stack([wls[k].astype(jnp.float32).reshape(C, P)
                     for k in _TAB_ROWS], axis=1)
    skip = wls["SKIP"].astype(jnp.int32).reshape(C, 1, P)
    n = wls["n"].astype(jnp.int32).reshape(C, 1, 1)
    cond = jnp.concatenate(
        [batches.astype(jnp.float32).reshape(C, 1),
         wls["BPE"].astype(jnp.float32).reshape(C, 1),
         hwrows.astype(jnp.float32).reshape(C, HW_FEATURE_DIM)],
        axis=1).reshape(C, 1, _COND_HW + HW_FEATURE_DIM)

    smem = lambda shape: pl.BlockSpec(shape, lambda c, g: (c, 0, 0),
                                      memory_space=pltpu.SMEM)
    blk = pl.BlockSpec((1, P, bp), lambda c, g: (c, 0, g))
    outs = pl.pallas_call(
        functools.partial(_fe_kernel, P=P),
        grid=(C, NP // bp),
        in_specs=[smem((1, len(_TAB_ROWS), P)), smem((1, 1, P)),
                  smem((1, 1, 1)), smem((1, 1, cond.shape[2])), blk],
        out_specs=[blk] * 7,
        out_shape=[jax.ShapeDtypeStruct((C, P, NP), jnp.float32)] * 6
        + [jax.ShapeDtypeStruct((C, P, NP), jnp.int32)],
        interpret=interpret,
    )(tab, skip, n, cond, strat_t)
    C_g, T_g, O_g, M_g, wave_g, glen, gid = (
        jnp.swapaxes(o, 1, 2)[:, :POP] for o in outs)
    hw = _cm.as_hw(hwrows)
    bc = lambda x: x[:, None, None]
    hwb = jax.tree_util.tree_map(bc, hw)
    out = _cm.finalize_groups(C_g, T_g, O_g, M_g, wave_g, glen,
                              budgets[:, None], hwb)
    return out, gid, M_g


_BP = 128                             # block-width cap (lanes per block)


def _block_size(pop: int, bp: int = _BP) -> int:
    """Block width: cover small populations with one block (padded to the
    next pow2 lane count), cap at ``bp``.  On the TPU a block narrower
    than the padded population must be a multiple of 128 lanes."""
    b = 8
    while b < pop and b < bp:
        b *= 2
    return b


def _call_grid(wls, strategies, batches, budgets, hw, *,
               bp: int | None, interpret: bool | None):
    """The one funnel to the jitted kernel.  ``interpret=None`` interprets
    on the CPU backend and compiles everywhere else, where a lowering
    failure raises; ``bp=None`` takes ``_block_size``'s width."""
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    strategies = jnp.asarray(strategies)
    C, POP, _ = strategies.shape
    return _fusion_eval_grid_jit(
        strategies, _kernel_wls(wls), jnp.asarray(batches),
        jnp.asarray(budgets), hw_array(stack_hw(hw, C)),
        bp=_block_size(POP, _BP if bp is None else bp), interpret=interpret)


def fusion_eval_grid(wls: dict, strategies, batches, budgets, hw, *,
                     bp: int | None = None, interpret: bool | None = None):
    """Pallas backend of ``cost_model.evaluate_grid`` (same contract):
    CostOut [C, POP] for strategies [C, POP, P] over stacked workloads,
    per-condition batches/budgets [C] and per-condition hardware (anything
    ``accel.stack_hw`` accepts).  Zero recompiles across accelerators for a
    fixed block shape — the hw row is traced kernel data.

    ``interpret=None`` interprets on the CPU and compiles elsewhere;
    ``bp=None`` takes ``_block_size``'s fixed width rule."""
    out, _, _ = _call_grid(wls, strategies, batches, budgets, hw, bp=bp,
                           interpret=interpret)
    return out


def fusion_eval_grid_stats(wls: dict, strategies, batches, budgets, hw, *,
                           bp: int | None = None,
                           interpret: bool | None = None):
    """Pallas backend of ``cost_model.evaluate_grid_stats``:
    ``(CostOut [C, POP], gid [C, POP, P], M_g [C, POP, P])`` — the group
    decomposition the G-Sampler repair operator consumes."""
    return _call_grid(wls, strategies, batches, budgets, hw, bp=bp,
                      interpret=interpret)


_KERNEL_KEYS = ("A", "W", "F", "OE", "UC", "SKIP", "n", "BPE")


def _kernel_wls(wls: dict) -> dict:
    """The packed-workload subset the kernel reads (mask is derived from
    ``n`` in-kernel; SHAPE6 is a decoration-only feature)."""
    missing = [k for k in _KERNEL_KEYS if k not in wls]
    if missing:
        raise KeyError(f"packed workload missing {missing} — pack with "
                       f"cost_model.pack_workload (BPE is required for the "
                       f"in-kernel rescale, DESIGN §13)")
    return {k: wls[k] for k in _KERNEL_KEYS}


def _lift(wl: dict):
    return {k: jnp.asarray(v)[None] for k, v in _kernel_wls(wl).items()}


def fusion_eval_population(strategies, wl: dict, *, batch, budget_bytes,
                           hw, bp: int | None = None,
                           interpret: bool | None = None):
    """Single-condition form: CostOut [pop] for strategies [pop, P] against
    one packed workload — ``cost_model.evaluate_population``'s contract.
    ``hw`` may be an AccelConfig or a traced ``accel.HwVec``."""
    out = fusion_eval_grid(
        _lift(wl), jnp.asarray(strategies)[None],
        jnp.asarray(batch, jnp.float32).reshape(1),
        jnp.asarray(budget_bytes, jnp.float32).reshape(1),
        stack_hw(hw, 1), bp=bp, interpret=interpret)
    return jax.tree_util.tree_map(lambda x: x[0], out)


def fusion_eval_population_stats(strategies, wl: dict, *, batch,
                                 budget_bytes, hw, bp: int | None = None,
                                 interpret: bool | None = None):
    """Single-condition stats form: ``(CostOut [pop], gid [pop, P],
    M_g [pop, P])`` — ``cost_model.evaluate_population_stats``'s contract."""
    out, gid, M_g = fusion_eval_grid_stats(
        _lift(wl), jnp.asarray(strategies)[None],
        jnp.asarray(batch, jnp.float32).reshape(1),
        jnp.asarray(budget_bytes, jnp.float32).reshape(1),
        stack_hw(hw, 1), bp=bp, interpret=interpret)
    return (jax.tree_util.tree_map(lambda x: x[0], out), gid[0], M_g[0])
