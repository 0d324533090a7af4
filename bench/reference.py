"""Plain reference of what the benchmark's cells serve.  Imports nothing of
the program under test.

- The paper's §3 fusion cost model (DNNFuser, arXiv:2201.11218), written
  from the equations as the repository's f64 oracle ``core/ref_model.py``
  states them, vectorized over strategies and computed in a chosen numpy
  dtype: float64 for the reference, ``ml_dtypes.bfloat16`` for the control
  that stands in for a lower-precision evaluator.
- The rollout environment's observation (paper Eq. 2 and §4.3.3): the
  conditioning reward and the state vector after a committed prefix.
- The inference-time budget guard (halve or sync until the staged prefix
  fits), as a function of the proposal.
- The decision transformer's full-sequence forward (paper §5.1: 3 blocks,
  2 heads, d_model 128) in ``jax.numpy`` float32 at ``Precision.HIGHEST``,
  or with every matmul operand rounded to float8_e4m3fn for the control.

The inputs are the request itself (the network's layer shapes, the batch,
the budget and the accelerator's published fields) and the weights the
benchmark made; nothing that the program computed.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import ml_dtypes
import numpy as np

SYNC = -1
UTIL_MIN = 1.0 / 4096.0
MB = float(2 ** 20)
BF16 = ml_dtypes.bfloat16

HW_FIELDS = ("npe", "pe_lanes", "freq_hz", "bw_offchip", "bw_onchip",
             "buf_bytes", "bytes_per_elem", "t_pass", "t_sync",
             "stream_buf_bytes")
# log-range normalization of the accelerator condition (DESIGN §11)
FEAT_LO = np.array([32, 1, 1e8, 1e8, 1e9, 0.25 * MB, 0.25, 1e-7, 1e-7,
                    0.0625 * MB], np.float64)
FEAT_HI = np.array([2 ** 20, 64, 1e10, 1e13, 1e14, 16384 * MB, 8.0, 1e-3,
                    1e-2, 1024 * MB], np.float64)
LOG_CAP = math.log1p(2 ** 24)


# ---------------------------------------------------------------- the request

def layer_arrays(workload, bytes_per_elem: float) -> dict:
    """Per-position float64 arrays of one chain, positions 0..n (0 = the
    network input): activation bytes per sample ``A``, weight bytes ``W``,
    MACs per sample ``F``, output elements ``OE``, utilization cap ``UC``,
    residual source ``SKIP`` and the 6-loop shape ``SHAPE6``."""
    layers = workload.layers
    n = len(layers)

    def pick(override, default):
        return float(override) if override is not None else float(default)

    A = np.zeros(n + 1)
    W = np.zeros(n + 1)
    F = np.zeros(n + 1)
    OE = np.ones(n + 1)
    UC = np.ones(n + 1)
    SKIP = np.full(n + 1, -1, np.int64)
    SHAPE6 = np.ones((n + 1, 6))
    A[0] = float(workload.input_elems) * bytes_per_elem
    SHAPE6[0] = workload.input_shape6
    for i, l in enumerate(layers, start=1):
        macs = pick(l.macs_override,
                    l.K * l.C * l.Y * l.X * l.R * l.S / l.groups)
        out = pick(l.out_elems_override, l.K * l.Y * l.X)
        w = pick(l.w_elems_override, l.K * l.C * l.R * l.S / l.groups)
        A[i] = out * bytes_per_elem
        W[i] = w * bytes_per_elem
        F[i] = macs
        OE[i] = max(out, 1.0)
        # depthwise convs lack channel-reduction parallelism (§3 util cap)
        UC[i] = 0.08 if (l.groups > 1 and l.groups == l.C) else 1.0
        SKIP[i] = l.skip_src
        SHAPE6[i] = (l.K, l.C, l.Y, l.X, l.R, l.S)
    return dict(A=A, W=W, F=F, OE=OE, UC=UC, SKIP=SKIP, SHAPE6=SHAPE6, n=n)


def hw_fields(accel) -> np.ndarray:
    return np.array([float(getattr(accel, f)) for f in HW_FIELDS],
                    np.float64)


def hw_features(accel) -> np.ndarray:
    """The accelerator condition vector the mapper reads, each field
    mapped log-linearly onto [0, 1] over its design range."""
    return np.log(hw_fields(accel) / FEAT_LO) / np.log(FEAT_HI / FEAT_LO)


# ------------------------------------------------------------- cost model §3

def evaluate(arr: dict, S, batch: float, accel, dt=np.float64) -> dict:
    """Cost of every strategy in ``S`` [K, n+1] (``SYNC`` = flush after that
    layer) on one chain: latency, peak staged-activation bytes, off-chip
    traffic and group count, each [K].  Every operation is rounded to
    ``dt``."""
    S = np.asarray(S, np.int64)
    K = S.shape[0]
    n = int(arr["n"])
    S = S[:, : n + 1]

    def c(x):
        return np.asarray(x, np.float64).astype(dt)

    A, W, F, OE, UC = (c(arr[k]) for k in ("A", "W", "F", "OE", "UC"))
    hw = {f: c(getattr(accel, f)) for f in HW_FIELDS}
    lanes = c(float(accel.npe) * float(accel.pe_lanes))
    peak_macs = c(float(accel.npe) * float(accel.pe_lanes)
                  * float(accel.freq_hz))
    Bi = int(batch)
    B = c(float(batch))
    one, zero, umin = c(1.0), c(0.0), c(UTIL_MIN)

    sync = np.zeros((K, n + 1), bool)
    sync[:, 1:] = S[:, 1:] < 0
    mb = c(np.minimum(np.maximum(S, 1), Bi))
    cs = np.cumsum(sync, axis=1)                    # syncs at positions <= i
    gid = np.zeros((K, n + 1), np.int64)
    gid[:, 1:] = cs[:, :-1]                         # syncs strictly before i
    glen = np.zeros((K, n + 2), np.int64)
    rows = np.repeat(np.arange(K), n)
    np.add.at(glen, (rows, gid[:, 1:].ravel()), 1)
    fused = np.take_along_axis(glen, gid, axis=1) > 1

    lat = np.zeros(K, dt)
    peak = np.zeros(K, dt)
    traffic = np.zeros(K, dt)
    groups = np.zeros(K, np.int64)
    g = {k: np.zeros(K, dt) for k in ("mem", "traf", "comp", "on", "waves")}
    for i in range(1, n + 1):
        head = np.full(K, i == 1) | (sync[:, i - 1] if i > 1 else False)
        tail = sync[:, i] | (i == n)
        fu, sy = fused[:, i], sync[:, i]
        if i - 1 >= 1:
            prev_mb = np.where(sync[:, i - 1], one, mb[:, i - 1])
        else:
            prev_mb = mb[:, 0]
        mbe = np.where(~fu, B, np.where(sy, prev_mb, mb[:, i])).astype(dt)
        stage = np.where(sy, one, mb[:, i]).astype(dt)
        w = np.ceil(B / mbe).astype(dt)
        m = (stage * A[i] + np.where(head, mbe * A[i - 1], zero)).astype(dt)
        t = (W[i] * w + np.where(head, B * A[i - 1], zero)
             + np.where(tail, B * A[i], zero)).astype(dt)
        src = int(arr["SKIP"][i])
        if src >= 0:
            lo = max(src, 1)
            crossing = (cs[:, i - 1] - cs[:, lo - 1]) > 0
            t = (t + np.where(crossing, c(2.0) * B * A[src], zero)).astype(dt)
            m = (m + np.where(crossing, zero, mbe * A[src])).astype(dt)
        m = np.where(fu, m, np.minimum(m, hw["stream_buf_bytes"])).astype(dt)
        util = np.minimum(np.maximum(mbe * OE[i] / lanes, umin), UC[i])
        comp = (B * F[i] / peak_macs / util.astype(dt)).astype(dt)
        on = (B * (A[i - 1] + A[i]) + W[i] * w).astype(dt)
        for k, v in (("mem", m), ("traf", t), ("comp", comp), ("on", on),
                     ("waves", w)):
            g[k] = (g[k] + v).astype(dt)
        L = (np.maximum(np.maximum(g["comp"], g["traf"] / hw["bw_offchip"]),
                        g["on"] / hw["bw_onchip"])
             + g["waves"] * hw["t_pass"] + hw["t_sync"]).astype(dt)
        lat = np.where(tail, lat + L, lat).astype(dt)
        traffic = np.where(tail, traffic + g["traf"], traffic).astype(dt)
        peak = np.where(tail, np.maximum(peak, g["mem"]), peak).astype(dt)
        groups = groups + tail
        for k in g:
            g[k] = np.where(tail, zero, g[k]).astype(dt)
    return dict(latency=lat.astype(np.float64), peak=peak.astype(np.float64),
                traffic=traffic.astype(np.float64), n_groups=groups)


def baseline(arr: dict, batch: float, accel) -> float:
    """No-fusion latency: every layer alone at the full batch (§3)."""
    A, W, F, OE, UC = (np.asarray(arr[k], np.float64)
                       for k in ("A", "W", "F", "OE", "UC"))
    B = float(batch)
    lanes = float(accel.npe) * float(accel.pe_lanes)
    peak_macs = lanes * float(accel.freq_hz)
    lat = 0.0
    for i in range(1, int(arr["n"]) + 1):
        util = min(max(B * OE[i] / lanes, UTIL_MIN), UC[i])
        comp = B * F[i] / peak_macs / util
        t = B * (A[i - 1] + A[i]) + W[i]
        lat += (max(comp, t / accel.bw_offchip, t / accel.bw_onchip)
                + accel.t_sync)
    return lat


def legal(strategy, n: int, batch: int) -> bool:
    """A strategy has n+1 entries, each SYNC or a micro-batch in
    1..batch, and the network input cannot sync."""
    s = np.asarray(strategy)
    return (s.shape == (n + 1,)
            and bool(np.all((s == SYNC) | ((s >= 1) & (s <= batch))))
            and int(s[0]) >= 1)


# --------------------------------------------------- environment and guard

def prefix_strategies(strategy) -> np.ndarray:
    """Row t: the strategy with positions < t applied, the rest SYNC."""
    s = np.asarray(strategy, np.int64)
    P = s.shape[0]
    keep = np.arange(P)[None, :] < np.arange(P)[:, None]
    return np.where(keep, s[None, :], SYNC)


def observations(arr: dict, strategy, batch: int, budget: float, accel):
    """(reward-to-go [n+1], state [n+1, 8]) the mapper reads at each step
    of the episode that committed ``strategy``: the share of the budget
    still free after the prefix, and the layer's log shape, the log
    budget and the log speedup of the prefix (paper Eq. 2, §4.3.3)."""
    n = int(arr["n"])
    pre = evaluate(arr, prefix_strategies(strategy), batch, accel)
    base = baseline(arr, batch, accel)
    rtg = np.maximum(0.0, (budget - pre["peak"]) / budget)
    states = np.zeros((n + 1, 8))
    states[:, :6] = np.log1p(arr["SHAPE6"][: n + 1]) / LOG_CAP
    states[:, 6] = math.log1p(budget / MB) / math.log1p(1024.0)
    states[:, 7] = np.log1p(base / np.maximum(pre["latency"], 1e-12))
    return rtg, states


def probe_peaks(arr: dict, strategy, batch: int, accel) -> np.ndarray:
    """[n+1, batch] peak bytes of the guard's probes: the committed prefix
    < t, micro-batch m at t, SYNC after (row t, column m-1)."""
    s = np.asarray(strategy, np.int64)
    n1 = s.shape[0]
    pre = prefix_strategies(s)                       # [n1, n1]
    probes = np.repeat(pre, batch, axis=0)           # [n1 * batch, n1]
    probes[np.arange(n1 * batch), np.repeat(np.arange(n1), batch)] = \
        np.tile(np.arange(1, batch + 1), n1)
    return evaluate(arr, probes, batch, accel)["peak"].reshape(n1, batch)


def guard(proposal: int, fits: np.ndarray) -> int:
    """The budget guard: halve the proposed micro-batch until the probe
    fits, and SYNC where even 1 does not.  ``fits[m-1]`` says whether
    micro-batch m fits."""
    a = proposal
    while a >= 1 and not fits[a - 1]:
        a = a // 2 if a > 1 else SYNC
    return a


def decode(y: float, batch: int) -> int:
    """The regression head's output to an action: SYNC below 0, else the
    nearest micro-batch in 1..batch (round half to even)."""
    if y < 0.0:
        return SYNC
    return int(min(max(np.rint(np.float32(y) * np.float32(batch)), 1),
                   batch))


def _interval(d: int, batch: int) -> tuple[float, float]:
    """Outputs of the head that decode to proposal ``d``."""
    if d == SYNC:
        return (-math.inf, 0.0)
    lo = 0.0 if d == 1 else (d - 0.5) / batch
    hi = math.inf if d == batch else (d + 0.5) / batch
    return (lo, hi)


def _distance(y: float, iv: tuple[float, float]) -> float:
    lo, hi = iv
    return max(lo - y, y - hi, 0.0)


NO_PREDICTION = 10.0   # gap of an action that no head output could give


def action_gap(y: float, action: int, t: int, batch: int, fits_variants
               ) -> float:
    """How far the reference's head output ``y`` at step ``t`` lies from
    every output that would have produced ``action`` (the regression
    head's analogue of a token's logit gap), in encoded-action units: one
    micro-batch step is 1/batch.  0 where the reference gives ``action``
    itself.  ``fits_variants`` holds the guard's fit vectors under which a
    proposal is judged (two where a probe sits on the budget's rounding
    edge)."""
    best = NO_PREDICTION
    for d in [SYNC] + list(range(1, batch + 1)):
        if t == 0:
            ok = max(d, 1) == action          # the input cannot sync
        else:
            ok = any(guard(d, f) == action for f in fits_variants)
        if ok:
            best = min(best, _distance(y, _interval(d, batch)))
    return best


def first_action(y: float, t: int, batch: int, fits: np.ndarray) -> int:
    """The action a head output ``y`` gives at step ``t``."""
    d = decode(y, batch)
    return max(d, 1) if t == 0 else guard(d, fits)


# ------------------------------------------------------- decision transformer

def _quantize(x):
    return x.astype(jnp.float8_e4m3fn).astype(jnp.float32)


def _mm(x, w, quant: bool):
    if quant:
        x, w = _quantize(x), _quantize(w)
    return jnp.matmul(x, w, precision=jax.lax.Precision.HIGHEST)


def _dense(p, x, quant):
    y = _mm(x, p["w"], quant)
    return y + p["b"] if "b" in p else y


def _layernorm(p, x):
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + 1e-5) * p["g"] + p["b"]


def _gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi)
                                     * (x + 0.044715 * x ** 3)))


def dt_forward(params, rtg, states, actions, hw, *, n_heads: int,
               quant: bool = False):
    """Teacher-forced head outputs [K, T] of the decision transformer over
    interleaved (reward, state, action) tokens; the output at step t is
    read from the state token of step t (paper §4.3).  ``quant`` rounds
    every matmul operand to float8_e4m3fn."""
    K, T = rtg.shape
    d = params["time"]["emb"].shape[1]
    hd = d // n_heads
    typ = params["type"]["emb"]
    time = params["time"]["emb"][:T][None]
    tok_r = (_dense(params["emb_r"], rtg[..., None], quant)
             + _dense(params["emb_h"], hw, quant)[:, None, :])
    tok_s = _dense(params["emb_s"], states, quant)
    tok_a = _dense(params["emb_a"], actions[..., None], quant)
    x = jnp.stack([tok_r + typ[0], tok_s + typ[1], tok_a + typ[2]],
                  axis=2) + time[:, :, None, :]
    L = 3 * T
    x = x.reshape(K, L, d)
    causal = jnp.tril(jnp.ones((L, L), bool))
    for blk in params["blocks"]:
        h = _layernorm(blk["ln1"], x)
        q, k, v = (_dense(blk["attn"][name], h, quant).reshape(K, L, n_heads,
                                                                hd)
                   for name in ("q", "k", "v"))
        if quant:
            q, k, v = _quantize(q), _quantize(k), _quantize(v)
        s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                       precision=jax.lax.Precision.HIGHEST) / math.sqrt(hd)
        s = jnp.where(causal, s, -1e30)
        pr = jax.nn.softmax(s, axis=-1)
        if quant:
            pr = _quantize(pr)
        o = jnp.einsum("bhqk,bkhd->bqhd", pr, v,
                       precision=jax.lax.Precision.HIGHEST).reshape(K, L, d)
        x = x + _dense(blk["attn"]["o"], o, quant)
        h = _layernorm(blk["ln2"], x)
        x = x + _dense(blk["mlp"]["down"],
                       _gelu(_dense(blk["mlp"]["up"], h, quant)), quant)
    x = _layernorm(params["ln_f"], x)
    s_tok = x.reshape(K, T, 3, d)[:, :, 1]
    return _dense(params["head"], s_tok, quant)[..., 0]


dt_forward_jit = jax.jit(dt_forward, static_argnames=("n_heads", "quant"))
