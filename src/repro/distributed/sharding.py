"""Partitioning rules: DP/FSDP x TP x EP x SP on the (pod, data, model) mesh.

Strategy (DESIGN §6):
 - TP ("model" axis): attention head projections, MLP hidden dim, the vocab
   dim of embeddings/heads, and the expert axis of MoE stacks (EP == TP
   axis: experts live where their weights live).
 - FSDP (the "data"/"pod" axes): every parameter additionally shards its
   largest remaining dim over the data axes — ZeRO-3 semantics; GSPMD
   inserts the per-layer all-gathers inside the scan (and the roofline's
   collective term prices them).
 - Batch dims of inputs shard over (pod, data).  SP: decode caches with
   global_batch < data-parallel size shard the *sequence* axis instead
   (long_500k), giving flash-decode-style distributed attention.

Rules are path-keyed (regex on the flattened param path), robust to the
leading stacked-layer axis.
"""
from __future__ import annotations

import re

import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from ..launch.mesh import dp_axes, batch_axes

__all__ = ["shard_spec_for_path", "param_specs", "batch_specs",
           "decode_state_specs_sharded", "logical_shard", "ambient_mesh",
           "data_parallel_mesh", "replicate_tree", "shard_leading_axis"]


def data_parallel_mesh(n_devices: int | None = None):
    """A 1-D ("data",) mesh over the local devices — the mapper trainer's
    mesh (DESIGN §10).  Unlike the (data, model) production mesh this always
    builds, even on a single-device CPU host, so the sharded train step is
    exercised by every smoke test."""
    import jax
    devs = jax.devices()[: n_devices or len(jax.devices())]
    return jax.sharding.Mesh(np.asarray(devs), ("data",))


def replicate_tree(tree, mesh):
    """Commit every leaf of ``tree`` fully replicated over ``mesh``.

    The serving replicas' parameter placement (DESIGN §14): one copy of
    the checkpointed params per device, so a tick sharded over the mesh
    finds its weights locally on every replica."""
    sh = NamedSharding(mesh, P())
    return jax.tree_util.tree_map(lambda x: jax.device_put(x, sh), tree)


def shard_leading_axis(tree, mesh, *, axis: str = "data"):
    """Commit every leaf of ``tree`` sharded over ``mesh`` along its
    leading axis (trailing dims replicated).

    This is how a formed serving tick fans out over engine replicas: the
    per-row condition arrays (stacked workloads, batches, budgets, hw
    rows) all carry the request-lane axis first, and the fused episode is
    an independent vmap over that axis, so GSPMD partitions it with zero
    cross-device communication — each replica rolls out its slice of the
    tick bit-identically to a single-device call.  Every leaf's leading
    dim must divide the mesh size (the engine pads ticks to guarantee
    it)."""
    n = int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
    sh = NamedSharding(mesh, P(axis))

    def put(x):
        x = np.asarray(x) if not isinstance(x, jax.Array) else x
        if x.ndim == 0 or x.shape[0] % n:
            raise ValueError(
                f"cannot shard leading axis of shape {getattr(x, 'shape', ())}"
                f" over {n} replicas; pad the tick to a multiple of {n}")
        return jax.device_put(x, sh)
    return jax.tree_util.tree_map(put, tree)


def ambient_mesh():
    """The mesh set by ``jax.set_mesh`` in the current context, or None."""
    am = jax.sharding.get_abstract_mesh()
    return None if am.empty else am


def logical_shard(x, *dims):
    """In-model sharding constraint with logical dim names.

    ``dims`` entries: "batch" (shard over the data-parallel axes), "model"
    (TP axis), "seq" (shard over 'data' — SP), or None.  A no-op when no
    mesh is in context (CPU smoke tests) or when the dim doesn't divide —
    so model code stays mesh-agnostic.  This is how we pin the layouts
    GSPMD otherwise gets wrong (e.g. vocab-dim of the logits: without the
    constraint it all-gathers a 262k-vocab f32 logits tensor per device).
    """
    am = ambient_mesh()
    if am is None or "model" not in am.axis_names:
        return x
    dp = tuple(a for a in am.axis_names if a != "model")
    dp_size = int(np.prod([am.shape[a] for a in dp]))
    spec = []
    for i, d in enumerate(dims):
        if d == "batch" and x.shape[i] % dp_size == 0:
            spec.append(dp if len(dp) > 1 else dp[0])
        elif d == "model" and x.shape[i] % am.shape["model"] == 0:
            spec.append("model")
        elif d == "seq" and x.shape[i] % am.shape["data"] == 0:
            spec.append("data")
        else:
            spec.append(None)
    return jax.lax.with_sharding_constraint(x, P(*spec))


def _path_str(path) -> str:
    parts = []
    for k in path:
        name = getattr(k, "key", None)
        parts.append(str(name if name is not None else getattr(k, "idx", k)))
    return "/".join(parts)


# (regex, (tp_dim_from_end, fsdp_dim_from_end)) — dims counted from the END
# of the shape so the rules are indifferent to the stacked-layer axis.
# tp None => no TP; fsdp None => no FSDP shard.
_RULES: list[tuple[str, tuple[int | None, int | None]]] = [
    (r"(^|/)embed/emb$",              (-2, -1)),   # [V, d]: V->model, d->data
    (r"(^|/)(tok|pos)/emb$",          (-2, -1)),
    (r"(^|/)head/w$",                 (-1, -2)),   # [d, V]: V->model
    (r"(^|/)(attn|xattn)/(q|k|v)/w$", (-1, -2)),   # [d, Hh]: heads->model
    (r"(^|/)(attn|xattn)/(q|k|v)/b$", (-1, None)),
    (r"(^|/)(attn|xattn)/o/w$",       (-2, -1)),   # [Hh, d]
    (r"(^|/)mlp/(gate|up)/w$",        (-1, -2)),   # [d, f]
    (r"(^|/)mlp/(gate|up)/b$",        (-1, None)),
    (r"(^|/)mlp/down/w$",             (-2, -1)),   # [f, d]
    # [E, d, f]: EP (E->model) when E divides tp; else expert-TP (f->model)
    (r"(^|/)moe/(gate|up)$",          (-3, -1)),
    (r"(^|/)moe/down$",               (-3, -1)),
    (r"(^|/)moe_tp/(gate|up)$",       (-1, -2)),   # rewritten rule target
    (r"(^|/)moe_tp/down$",            (-2, -1)),
    (r"(^|/)moe/router/w$",           (None, None)),
    # rwkv time/channel mix
    (r"(^|/)(r|k|v|g|cr|ck)/w$",      (-1, -2)),
    (r"(^|/)(o|cv)/w$",               (-2, -1)),
    (r"(^|/)(w1|w2)/w$",              (None, -1)),
    # hymba ssm: small per-channel params, replicate
    (r"(^|/)ssm/",                    (None, None)),
]


# Paths whose TP shard is only legal when the HEAD COUNT (not the packed
# feature dim!) divides the TP size: sharding [d, H*hd] when H < tp would
# split head_dim and turn every attention contraction into an all-reduce
# (we measured 250 GB/device of score all-reduces on gemma3 before this
# gate).  When heads don't divide, the projection is replicated across
# 'model' (Megatron GQA practice) and FSDP still shards its storage.
_Q_PATHS = re.compile(r"(^|/)(attn|xattn)/(q/w|q/b|o/w)$")
_KV_PATHS = re.compile(r"(^|/)(attn|xattn)/(k|v)/(w|b)$")
_RWKV_HEAD_PATHS = re.compile(r"(^|/)(r|k|v|g|o)/w$")


def shard_spec_for_path(path_str: str, shape: tuple[int, ...], mesh,
                        cfg=None) -> P:
    """PartitionSpec for one param leaf (divisibility-checked)."""
    fsdp = dp_axes(mesh)
    fsdp_size = int(np.prod([mesh.shape[a] for a in fsdp]))
    tp_size = mesh.shape["model"]
    ndim = len(shape)
    spec = [None] * ndim

    tp_vetoed = False
    if cfg is not None:
        if _Q_PATHS.search(path_str) and cfg.n_heads % tp_size:
            tp_vetoed = True
        if _KV_PATHS.search(path_str) and "attn" in path_str \
                and cfg.kv_heads % tp_size:
            tp_vetoed = True
        if cfg.family == "ssm" and _RWKV_HEAD_PATHS.search(path_str) \
                and cfg.n_heads % tp_size:
            tp_vetoed = True
        # grok-style MoE (E=8 < tp=16): fall back to Megatron expert-TP —
        # shard each expert's hidden dim instead of the expert axis.
        if "/moe/" in path_str and cfg.n_experts % tp_size:
            path_str = path_str.replace("/moe/", "/moe_tp/")

    for pat, (tp_d, fs_d) in _RULES:
        if re.search(pat, path_str):
            if tp_d is not None and -tp_d <= ndim \
                    and shape[tp_d] % tp_size == 0 and not tp_vetoed:
                spec[ndim + tp_d] = "model"
            if fs_d is not None and -fs_d <= ndim \
                    and spec[ndim + fs_d] is None \
                    and shape[fs_d] % fsdp_size == 0:
                spec[ndim + fs_d] = fsdp if len(fsdp) > 1 else fsdp[0]
            return P(*spec)
    return P()      # norms, scalars, unmatched -> replicated


def param_specs(params, mesh, cfg=None):
    """Pytree of PartitionSpecs matching ``params`` (arrays or SDS)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    specs = [shard_spec_for_path(_path_str(p), v.shape, mesh, cfg)
             for p, v in flat]
    return jax.tree_util.tree_unflatten(treedef, specs)


def batch_specs(batch, mesh, *, shard_seq: bool = False):
    """Specs for a model-input batch: leading batch dim over (pod, data);
    if ``shard_seq`` (long-context, batch < dp size), shard dim 1 (seq)."""
    ba = batch_axes(mesh)
    dp = dp_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))

    def spec(x):
        if x.ndim == 0:
            return P()
        if shard_seq and x.ndim >= 2 and x.shape[0] == 1 \
                and x.shape[1] % dp_size == 0:
            return P(None, ba, *([None] * (x.ndim - 2)))
        if x.shape[0] % dp_size:
            return P()                     # batch-1 decode: replicate
        return P(ba, *([None] * (x.ndim - 1)))
    return jax.tree_util.tree_map(spec, batch)


def decode_state_specs_sharded(state_specs, mesh, *, shard_seq: bool = False):
    """Specs for stacked decode caches [L, B, T, kvh, hd].

    Normal decode: batch over (pod, data) AND the cache sequence axis over
    'model' — distributed flash-decode (GSPMD inserts the tiny cross-shard
    softmax reductions; kv-head counts are < TP size for every GQA arch, so
    the head axis cannot carry the shard).  Without the seq shard a grok-1
    32k cache is 69 GB/device.  SP mode (``shard_seq``, long-context
    batch=1): the sequence axis shards over 'data' as well.
    """
    ba = batch_axes(mesh)
    dp = dp_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    tp = mesh.shape["model"]

    def spec(path, x):
        name = str(getattr(path[-1], "key", "")) if path else ""
        if x.ndim <= 1:
            return P()
        if name == "memory":                # whisper enc memory [B, T, d]
            return P(ba if x.shape[0] % dp_size == 0 else None, None, None)
        if x.ndim == 2:                     # [L, B]-style
            return (P(None, ba) if not shard_seq
                    and x.shape[1] % dp_size == 0 else P())
        if shard_seq:
            # [L, B=1, T, ...]: shard T over data+model; small states repl.
            if x.ndim >= 3 and x.shape[1] == 1 and x.shape[2] % \
                    (mesh.shape["data"] * tp) == 0:
                return P(None, None, ("data", "model"),
                         *([None] * (x.ndim - 3)))
            return P()
        b = ba if x.shape[1] % dp_size == 0 else None
        seq = "model" if x.ndim >= 5 and x.shape[2] % tp == 0 else None
        return P(None, b, seq, *([None] * (x.ndim - 3)))

    flat, treedef = jax.tree_util.tree_flatten_with_path(state_specs)
    return jax.tree_util.tree_unflatten(
        treedef, [spec(p, v) for p, v in flat])
