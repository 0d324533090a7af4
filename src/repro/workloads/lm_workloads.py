"""Lower an assigned ArchConfig to a fusion-mapper Workload (beyond-paper).

The paper maps CNN chains; here every assigned LM architecture becomes a
chain at transformer-block granularity — the granularity at which
inter-layer fusion (FLAT-style activation staging across blocks) operates.
Per block: MACs = the block's matmul work per *sample* (one sequence for
train/prefill, one token for decode — where the fusible axis is the
sequence-chunk/batch of requests, DESIGN §5), staged activation = the
block-boundary hidden state, weights = the block's parameters (ALL experts
for MoE — residency is what fusion must budget, which is why the mapper
learns to sync around expert blocks).

A config with latent attention (``kv_lora_rank``: DeepSeek-V3) is lowered
for prefill one step finer, one position per sublayer
(:func:`mla_prefill_workload`).
"""
from __future__ import annotations

from ..configs import ArchConfig
from .layer import Layer, Workload

__all__ = ["lm_workload", "mla_prefill_workload"]


def _block_stats(cfg: ArchConfig, seq: int, per_token: bool):
    """(macs, w_elems) per sample for one decoder block."""
    d, hd = cfg.d_model, cfg.hd
    toks = 1 if per_token else seq
    attn_w = d * (cfg.n_heads * hd) + 2 * d * (cfg.kv_heads * hd) \
        + (cfg.n_heads * hd) * d
    attn_macs = toks * attn_w
    # attention itself: per token attends to `seq` keys (cache len)
    kv_span = seq
    attn_macs += 2.0 * toks * kv_span * cfg.n_heads * hd
    if cfg.n_experts:
        w_ffn = cfg.n_experts * 3 * d * cfg.d_ff
        macs_ffn = toks * cfg.moe_top_k * 3 * d * cfg.d_ff
    elif cfg.family == "ssm":
        w_ffn = d * cfg.d_ff + cfg.d_ff * d + d * d     # channel mix + gate
        macs_ffn = toks * w_ffn
        attn_w = 4 * d * d                               # r,k,v,o time-mix
        attn_macs = toks * attn_w + toks * d * hd        # wkv update
    else:
        mult = 3 if cfg.mlp_kind == "swiglu" else 2
        w_ffn = mult * d * cfg.d_ff
        macs_ffn = toks * w_ffn
    if cfg.family == "hybrid":
        w_ffn += 2 * d * d + d * 2 * cfg.ssm_state
        macs_ffn += toks * (2 * d * d)
    return float(attn_macs + macs_ffn), float(attn_w + w_ffn)


def lm_workload(cfg: ArchConfig, *, seq_len: int, batch: int,
                mode: str = "train") -> Workload:
    """One Workload layer per transformer block (+ embed & head); a config
    with latent attention gets the sublayer prefill chain."""
    if cfg.kv_lora_rank:
        if mode != "prefill":
            raise ValueError(f"{cfg.name}: latent attention is lowered for "
                             f"prefill only, not {mode!r}")
        return mla_prefill_workload(cfg, seq_len=seq_len, batch=batch)
    per_token = (mode == "decode")
    toks = 1 if per_token else seq_len
    d = cfg.d_model
    layers: list[Layer] = []
    # embed: per sample act = toks x d
    layers.append(Layer.op(
        "embed", macs=float(toks * d), out_elems=float(toks * d),
        w_elems=float(cfg.vocab_padded * d),
        shape6=(d, cfg.vocab_padded, toks, 1, 1, 1)))
    macs, w = _block_stats(cfg, seq_len, per_token)
    n_blocks = cfg.n_layers + (cfg.encoder_layers if cfg.family == "encdec"
                               else 0)
    for i in range(n_blocks):
        layers.append(Layer.op(
            f"block{i}", macs=macs, out_elems=float(toks * d), w_elems=w,
            shape6=(d, d, toks, 1, cfg.d_ff // max(d, 1) + 1, 1)))
    layers.append(Layer.op(
        "head", macs=float(toks * d * cfg.vocab_padded),
        out_elems=float(toks * cfg.vocab_padded),
        w_elems=float(d * cfg.vocab_padded),
        shape6=(cfg.vocab_padded, d, toks, 1, 1, 1)))
    return Workload(f"{cfg.name}_{mode}", layers,
                    input_elems=float(toks),
                    input_shape6=(1, 1, toks, 1, 1, 1),
                    default_batch=batch)


def mla_prefill_workload(cfg: ArchConfig, *, seq_len: int,
                         batch: int = 64) -> Workload:
    """The prefill of one prompt of ``seq_len`` tokens through an MLA + MoE
    model (DeepSeek-V3) as a chain of sublayers: the embedded tokens, then
    per layer ``attn{l}`` (RMSNorm, the latent q/kv projections, causal
    attention, o, residual) and ``ffn{l}`` (dense SwiGLU, the first
    ``first_k_dense_replace`` layers) or ``moe{l}`` (router, shared
    experts, this chip's routed experts), then the MTP module (``mtp.attn``
    over [norm(h); norm(emb(t+1))], which reads the embedded tokens through
    a skip edge, and ``mtp.moe``) and ``head``: the shared LM head on the
    last token of the main model (a skip edge to its last hidden state) and
    of the MTP module.

    A sublayer's residual is its own input, which the §3 model already
    holds in the group.  The embedding is a gather of ``seq_len`` rows,
    charged as the input tensor and not as a read of the table.  Under
    expert parallelism over ``n_experts / experts_held`` chips this chip
    holds ``cfg.experts_held`` routed experts (0: all); their all-to-all is
    not modelled (the §3 model has no interconnect term)."""
    if cfg.mtp_layers > 1:
        raise ValueError(f"{cfg.name}: one MTP module is lowered, not "
                         f"{cfg.mtp_layers}")
    held = cfg.experts_held or cfg.n_experts
    if cfg.n_experts % held:
        raise ValueError(f"{held} experts held do not divide "
                         f"{cfg.n_experts}")
    S, d, H, V = seq_len, cfg.d_model, cfg.n_heads, cfg.vocab
    qk = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
    # q_a, q_b, kv_a (latent + the shared rotary key), kv_b (k_nope and v
    # per head), o; and the input, q_a and kv_a RMSNorm gains
    mla = (d * cfg.q_lora_rank + cfg.q_lora_rank * H * qk
           + d * (cfg.kv_lora_rank + cfg.qk_rope_head_dim)
           + cfg.kv_lora_rank * H * (cfg.qk_nope_head_dim + cfg.v_head_dim)
           + H * cfg.v_head_dim * d)
    norms = d + cfg.q_lora_rank + cfg.kv_lora_rank
    # causal attention: query i attends keys 0..i, S(S+1)/2 pairs, each
    # qk MACs for the score and v for the weighted value, per head
    core = H * (qk + cfg.v_head_dim) * S * (S + 1) // 2
    expert = 3 * d * cfg.moe_d_ff
    # the router and the shared experts, which every token runs
    every_token = d * cfg.n_experts + cfg.n_shared_experts * expert
    eh = 2 * d * d                        # MTP's eh_proj over [h; emb]
    # (weight elems, MACs, 6-loop view: out features, the inner width,
    # tokens, 1, parallel units (heads; experts a token uses), 1)
    kinds = {
        "attn": (mla + norms, mla * S + core,
                 (d, H * cfg.v_head_dim, S, 1, H, 1)),
        # eh_proj, its enorm and hnorm gains, and an MLA sublayer
        "mtp.attn": (eh + 2 * d + mla + norms, (eh + mla) * S + core,
                     (d, 2 * d, S, 1, H, 1)),
        "ffn": (3 * d * cfg.d_ff, 3 * d * cfg.d_ff * S,
                (d, cfg.d_ff, S, 1, 1, 1)),
        # the held experts' weights; under balanced routing they receive
        # the S * top_k token-expert pairs this chip's tokens send out
        "moe": (every_token + held * expert,
                (every_token + cfg.moe_top_k * expert) * S,
                (d, cfg.moe_d_ff, S, 1,
                 cfg.moe_top_k + cfg.n_shared_experts, 1)),
    }

    def op(name: str, kind: str, skip_src: int = -1) -> Layer:
        w, macs, shape6 = kinds[kind]
        return Layer.op(name, macs=float(macs), out_elems=float(S * d),
                        w_elems=float(w), shape6=shape6, skip_src=skip_src)

    layers: list[Layer] = []
    for l in range(cfg.n_layers):
        layers.append(op(f"attn{l}", "attn"))
        if l < cfg.first_k_dense_replace:
            layers.append(op(f"ffn{l}", "ffn"))
        else:
            layers.append(op(f"moe{l}", "moe"))
    h_main = len(layers)                  # position of the last hidden state
    if cfg.mtp_layers:
        layers.append(op("mtp.attn", "mtp.attn", skip_src=0))
        layers.append(op("mtp.moe", "moe"))
    heads = 1 + cfg.mtp_layers
    layers.append(Layer.op(
        "head", macs=float(heads * V * d), out_elems=float(heads * V),
        w_elems=float(V * d), shape6=(V, d, heads, 1, 1, 1),
        skip_src=h_main if cfg.mtp_layers else -1))
    kib = f"{S // 1024}k" if S % 1024 == 0 else str(S)
    return Workload(f"{cfg.name}_prefill_{kib}", layers,
                    input_elems=float(S * d), input_shape6=(d, d, S, 1, 1, 1),
                    default_batch=batch)
