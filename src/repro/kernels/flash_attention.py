"""Flash attention (TPU Pallas): blocked online-softmax, causal + sliding
window + GQA.

Tiling: grid = (batch, q_heads, S/bq); each program holds one (bq, hd) query
block in VMEM, loops over (bk, hd) KV blocks of its kv-head with the online
softmax recurrence (m, l, acc in VMEM scratch), and writes one output block.
GQA is expressed in the kv BlockSpec index_map (q-head h reads kv-head
h // group).  MXU alignment: bq/bk multiples of the 128 lane width; hd is
the natural minor dim.  Causality/window prune whole KV blocks via the
loop's upper bound.  Validated on CPU with interpret=True against
``ref.attention_ref`` (see tests/test_kernels.py); on TPU it is selected by
``attn_impl=pallas``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

__all__ = ["flash_attention"]

NEG_INF = -1e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, *, bq: int, bk: int, seq_k: int,
               causal: bool, window: int, sm_scale: float):
    qi = pl.program_id(2)
    q = q_ref[...].astype(jnp.float32) * sm_scale          # [bq, hd]
    nkv = seq_k // bk

    # block-level pruning bounds
    q_lo = qi * bq
    q_hi = q_lo + bq - 1
    if causal:
        hi = jnp.minimum(nkv, (q_hi // bk) + 1)
    else:
        hi = nkv
    if window > 0:
        lo = jnp.maximum(0, (q_lo - window + 1) // bk)
    else:
        lo = 0

    def body(j, carry):
        m, l, acc = carry
        k = k_ref[pl.ds(j * bk, bk), :].astype(jnp.float32)  # [bk, hd]
        v = v_ref[pl.ds(j * bk, bk), :].astype(jnp.float32)
        s = q @ k.T                                         # [bq, bk]
        ids_q = q_lo + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        ids_k = j * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= ids_k <= ids_q
        if window > 0:
            mask &= (ids_q - ids_k) < window
        s = jnp.where(mask, s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[:, None])
        alpha = jnp.exp(m - m_new)
        l_new = l * alpha + p.sum(-1)
        acc = acc * alpha[:, None] + p @ v
        return m_new, l_new, acc

    m0 = jnp.full((bq,), NEG_INF, jnp.float32)
    l0 = jnp.zeros((bq,), jnp.float32)
    acc0 = jnp.zeros((bq, q_ref.shape[-1]), jnp.float32)
    m, l, acc = jax.lax.fori_loop(lo, hi, body, (m0, l0, acc0))
    o_ref[...] = (acc / jnp.maximum(l, 1e-30)[:, None]).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "window", "bq", "bk",
                                             "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True, window: int = -1, bq: int = 128,
                    bk: int = 128, interpret: bool | None = None) -> jax.Array:
    """q [B,S,Hq,hd], k/v [B,T,Hkv,hd] -> [B,S,Hq*hd].

    S and T must be multiples of bq/bk (the launchers pad); ``window`` is a
    *static* int here (the XLA path accepts traced windows; kernels are
    specialized per window value).
    """
    B, S, Hq, hd = q.shape
    T, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    sm_scale = 1.0 / (hd ** 0.5)

    qt = q.transpose(0, 2, 1, 3)        # [B, Hq, S, hd]
    kt = k.transpose(0, 2, 1, 3)        # [B, Hkv, T, hd]
    vt = v.transpose(0, 2, 1, 3)

    grid = (B, Hq, S // bq)
    out = pl.pallas_call(
        functools.partial(_fa_kernel, bq=bq, bk=bk, seq_k=T, causal=causal,
                          window=int(window), sm_scale=sm_scale),
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, None, bq, hd), lambda b, h, i: (b, h, i, 0)),
            pl.BlockSpec((None, None, T, hd),
                         lambda b, h, i: (b, h // G, 0, 0)),
            pl.BlockSpec((None, None, T, hd),
                         lambda b, h, i: (b, h // G, 0, 0)),
        ],
        out_specs=pl.BlockSpec((None, None, bq, hd),
                               lambda b, h, i: (b, h, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Hq, S, hd), q.dtype),
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3).reshape(B, S, Hq * hd)
