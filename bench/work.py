"""Work counted from shapes, for the utilization and roofline metrics.

Two counts:

- the decision transformer's matmul FLOPs per rollout step (paper §5.1:
  3 blocks, 2 heads, d_model 128, d_ff 512), 2 FLOPs per multiply-add.
  Step 0 feeds (r_0, s_0); step t >= 1 feeds (a_{t-1}, r_t, s_t).  A token
  at flat position p attends over the p+1 tokens up to it; masked
  positions of the cache and padded lanes do not count.  Layer norms,
  softmax and GELU are elementwise and not counted, as usual for MFU.
- the operations and bytes of one §3 cost evaluation of one candidate
  strategy (DNNFuser, arXiv:2201.11218, §3; the equations as
  ``core/ref_model.py`` states them), counted from those equations per
  real layer position and per fused group.
"""
from __future__ import annotations

# Operations of the §3 equations per real layer position, as written in
# the plain reference (bench/reference.py, evaluate):
#   effective micro-batch (clip to [1, B], producer's at a sync)   4
#   waves = ceil(B / mbe)                                           2
#   staged memory  stage*A_i + mbe*A_{i-1}                          3
#   off-chip traffic W_i*waves + B*A_{i-1} + B*A_i                  5
#   residual edge: crossing test, 2*B*A_src or mbe*A_src            4
#   streaming-buffer clamp of an unfused layer                      1
#   utilization clip(mbe*OE_i/lanes, 1/4096, UC_i)                  4
#   compute time B*F_i/peak/util                                    3
#   on-chip bytes B*(A_{i-1}+A_i) + W_i*waves                       4
#   group sums of memory, traffic, compute, on-chip bytes, waves    5
EVAL_OPS_PER_POSITION = 35
# per fused group: roofline max of 3 terms (2 divides, 2 maxima), pipeline
# and sync overheads (2), latency sum, peak max, traffic sum (3)
EVAL_OPS_PER_GROUP = 9
# bytes one evaluation must move: its strategy (int32 per real position)
# in; latency, peak and validity (4 bytes each) out.  The chain's own
# arrays are shared by the whole population and not counted.
EVAL_BYTES_PER_POSITION = 4
EVAL_BYTES_OUT = 12


def eval_ops(n: int) -> int:
    """Operations of one evaluation on a chain of n layers, at its most
    groups (every layer its own group)."""
    return EVAL_OPS_PER_POSITION * n + EVAL_OPS_PER_GROUP * n


def eval_bytes(n: int) -> int:
    return EVAL_BYTES_PER_POSITION * (n + 1) + EVAL_BYTES_OUT


def dt_token_flops(cfg: dict, attended: int) -> int:
    """Matmul FLOPs of one token through the blocks: Q, K, V and output
    projections, QK^T and AV over ``attended`` tokens, and the MLP."""
    d, dff = cfg["d_model"], cfg["d_ff"]
    per_block = (2 * 4 * d * d           # q, k, v, o projections
                 + 2 * 2 * d * attended  # scores and weighted values
                 + 2 * 2 * d * dff)      # up and down projections
    return cfg["n_blocks"] * per_block


def dt_step_flops(cfg: dict, t: int) -> int:
    """FLOPs of rollout step t: embed the step's tokens, run them through
    the blocks, and read the action from the last one's head."""
    d = cfg["d_model"]
    if t == 0:
        embeds = 2 * d * (1 + cfg["hw_dim"]) + 2 * d * 8      # r_0 (+hw), s_0
        positions = (0, 1)
    else:
        embeds = 2 * d * 1 + 2 * d * (1 + cfg["hw_dim"]) + 2 * d * 8
        positions = (3 * t - 1, 3 * t, 3 * t + 1)             # a, r, s
    body = sum(dt_token_flops(cfg, p + 1) for p in positions)
    return embeds + body + 2 * d                              # head


def dt_episode_flops(cfg: dict, n: int) -> int:
    """FLOPs of the n+1 real steps of one request on an n-layer chain."""
    return sum(dt_step_flops(cfg, t) for t in range(n + 1))
