"""Plain reference of the DeepSeek-V3 prefill chains the mapper is asked
to map.  Imports nothing of the program under test.

The networks ``deepseek_v3_prefill_4k``, ``_16k`` and ``_32k`` are one
prompt of 4,096, 16,384 or 32,768 tokens through DeepSeek-V3, one chain
position per sublayer; their per-position arrays are built here from the
numbers of the model's config.json, written out below, and the equations
of each sublayer's weights and multiply-adds.  Every other network, the
§3 cost model, the environment, the guard and the decision transformer
are ``bench/reference.py``'s.

The chain is one chip's share of DeepSeek-V3's prefill unit (tech report,
arXiv:2412.19437, §3.4.1): 32 chips, each MoE layer in EP32, so the chip
holds 8 of the 256 routed experts.  Attention, the dense layers, the MTP
projection and the head are replicated per chip (data-parallel attention,
where the report uses TP4).  The embedding is a gather of the prompt's
rows, charged as the input tensor; the all-to-all is not modelled.
"""
from __future__ import annotations

import numpy as np

from bench import reference as base
from bench.reference import *  # noqa: F401,F403  the rest of the reference

# https://huggingface.co/deepseek-ai/DeepSeek-V3/blob/main/config.json
HIDDEN = 7168                 # hidden_size
LAYERS = 61                   # num_hidden_layers
HEADS = 128                   # num_attention_heads
Q_LORA = 1536                 # q_lora_rank
KV_LORA = 512                 # kv_lora_rank
QK_NOPE = 128                 # qk_nope_head_dim
QK_ROPE = 64                  # qk_rope_head_dim
V_HEAD = 128                  # v_head_dim
DENSE_LAYERS = 3              # first_k_dense_replace
DENSE_WIDTH = 18432           # intermediate_size
ROUTED = 256                  # n_routed_experts
TOP_K = 8                     # num_experts_per_tok
EXPERT_WIDTH = 2048           # moe_intermediate_size
SHARED = 1                    # n_shared_experts
VOCAB = 129280                # vocab_size
HELD = 8                      # routed experts on this chip (EP32)

PROMPT_TOKENS = {"deepseek_v3_prefill_4k": 4096,
                 "deepseek_v3_prefill_16k": 16384,
                 "deepseek_v3_prefill_32k": 32768}


def sublayers(S: int) -> list[tuple]:
    """(name, weight elems, MACs per prompt, output elems, skip source,
    6-loop shape) of chain positions 1..125 for a prompt of S tokens."""
    d = HIDDEN
    qk = QK_NOPE + QK_ROPE
    q_a = d * Q_LORA
    q_b = Q_LORA * HEADS * qk
    kv_a = d * (KV_LORA + QK_ROPE)
    kv_b = KV_LORA * HEADS * (QK_NOPE + V_HEAD)
    o = HEADS * V_HEAD * d
    mla = q_a + q_b + kv_a + kv_b + o
    mla_norms = d + Q_LORA + KV_LORA        # input, q_a and kv_a RMSNorms
    # causal: S(S+1)/2 (query, key) pairs, each qk + v MACs in every head
    core = HEADS * (qk + V_HEAD) * (S * (S + 1) // 2)
    swiglu = 3 * d * DENSE_WIDTH
    expert = 3 * d * EXPERT_WIDTH
    router = d * ROUTED
    moe_w = router + SHARED * expert + HELD * expert
    moe_f = (router + SHARED * expert + TOP_K * expert) * S
    eh = 2 * d * d
    attn_shape = (d, HEADS * V_HEAD, S, 1, HEADS, 1)
    moe_shape = (d, EXPERT_WIDTH, S, 1, TOP_K + SHARED, 1)
    out = []
    for layer in range(LAYERS):
        out.append((f"attn{layer}", mla + mla_norms, mla * S + core, S * d,
                    -1, attn_shape))
        if layer < DENSE_LAYERS:
            out.append((f"ffn{layer}", swiglu, swiglu * S, S * d, -1,
                        (d, DENSE_WIDTH, S, 1, 1, 1)))
        else:
            out.append((f"moe{layer}", moe_w, moe_f, S * d, -1, moe_shape))
    h_main = len(out)
    # MTP: eh_proj over [norm(h); norm(emb(t+1))], the embedded tokens
    # coming from position 0, then an MLA and an MoE sublayer
    out.append(("mtp.attn", eh + 2 * d + mla + mla_norms,
                (eh + mla) * S + core, S * d, 0,
                (d, 2 * d, S, 1, HEADS, 1)))
    out.append(("mtp.moe", moe_w, moe_f, S * d, -1, moe_shape))
    # the shared head on the last token of the main model and of MTP
    out.append(("head", VOCAB * d, 2 * VOCAB * d, 2 * VOCAB, h_main,
                (VOCAB, d, 2, 1, 1, 1)))
    return out


def layer_arrays(workload, bytes_per_elem: float) -> dict:
    """``bench/reference.py``'s ``layer_arrays``; a DeepSeek-V3 prefill
    chain's from the numbers above and not from the request's layers."""
    S = PROMPT_TOKENS.get(workload.name)
    if S is None:
        return base.layer_arrays(workload, bytes_per_elem)
    rows = sublayers(S)
    n = len(rows)
    A = np.zeros(n + 1)
    W = np.zeros(n + 1)
    F = np.zeros(n + 1)
    OE = np.ones(n + 1)
    UC = np.ones(n + 1)
    SKIP = np.full(n + 1, -1, np.int64)
    SHAPE6 = np.ones((n + 1, 6))
    A[0] = float(S * HIDDEN) * bytes_per_elem
    SHAPE6[0] = (HIDDEN, HIDDEN, S, 1, 1, 1)
    for i, (_, w, f, oe, src, shape) in enumerate(rows, start=1):
        A[i] = float(oe) * bytes_per_elem
        W[i] = float(w) * bytes_per_elem
        F[i] = float(f)
        OE[i] = float(oe)
        SKIP[i] = src
        SHAPE6[i] = shape
    return dict(A=A, W=W, F=F, OE=OE, UC=UC, SKIP=SKIP, SHAPE6=SHAPE6, n=n)
