"""deepseek-v3 [moe] (hf:deepseek-ai/DeepSeek-V3, config.json).

61 layers, d_model=7168; multi-head latent attention with 128 heads,
q_lora_rank 1536, kv_lora_rank 512, qk 128 + 64 rotary, v 128; the first 3
layers dense at width 18432, then 58 MoE layers of 256 routed experts
(top-8, width 2048) and 1 shared expert; one multi-token-prediction
module; vocab 129280, untied head.

Mapped as the prefill of one chip of DeepSeek-V3's prefill unit (tech
report, arXiv:2412.19437, §3.4.1): 32 chips with EP32, so this chip holds
8 of each MoE layer's 256 routed experts (``experts_held``); the router
keeps its 256 outputs and top-8.  Departure from the report: attention,
the dense layers, the MTP projection and the head are replicated per chip
(data-parallel attention), where the report runs TP4 attention.
"""
from .base import ArchConfig

CONFIG = ArchConfig(
    name="deepseek_v3", family="moe",
    n_layers=61, d_model=7168, n_heads=128, kv_heads=128, d_ff=18432,
    vocab=129280, n_experts=256, moe_top_k=8, moe_d_ff=2048,
    n_shared_experts=1, first_k_dense_replace=3, experts_held=8,
    q_lora_rank=1536, kv_lora_rank=512, qk_nope_head_dim=128,
    qk_rope_head_dim=64, v_head_dim=128, mtp_layers=1,
    source="hf:deepseek-ai/DeepSeek-V3 config.json")
