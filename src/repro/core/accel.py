"""Accelerator model: config, zoo presets, and the traced hardware vector.

The paper's configuration (§5.1): 1024 PEs, 64 MB on-chip buffer, 900 GB/s
off-chip BW, 9000 GB/s on-chip BW, 1 GHz.

Hardware-adaptation note (see DESIGN.md §4): taken literally (1 MAC/PE/cycle
= 2 GOPS against 900 GB/s) every CNN in the paper sits ~200x inside the
compute-bound roofline region, where layer fusion cannot produce the
reported 1.2x-3.1x speedups; and the paper's own Fig. 4 strategies
(micro-batch 36 staged under a 20 MB budget on ResNet18) are only
memory-consistent with 1-byte activations and an activation-only buffer
constraint.  We therefore model the paper's *observed regime*: an edge-class
int8 accelerator (1024 PEs x 4-lane vector MAC = 8.2 TOPS, LPDDR-class
8 GB/s off-chip, 40 GB/s on-chip), activations quantized to 1 byte, the on-chip buffer constraint
applying to staged activations (a separate streaming path feeds weights,
re-fetched once per micro-batch wave).  All constants are config fields.

Hardware as a CONDITION (DESIGN.md §11): the mapper generalizes over
accelerators, so the hardware descriptor must be *data*, not a baked-in
constant.  Three representations, all interconvertible:

 - :class:`AccelConfig` — the frozen host-side dataclass (Python floats);
 - :class:`HwVec` — the same fields as a NamedTuple of ``jnp`` scalars (a
   pytree), so the cost model traces through it and ``vmap`` runs over a
   *batch of accelerators*; ``stack_hw`` builds the per-condition form;
 - ``accel_features`` — a normalized (log-range, each field mapped to
   [0, 1]) feature vector that conditions the learned mapper; it is
   invertible (``accel_from_features``) so checkpoints carry no hidden
   normalization state.

``ACCEL_ZOO`` holds named design points spanning embedded to
datacenter-class devices — the train/hold-out axis of the
hardware-generalization benchmark (``benchmarks/table_hw_generalization``).
"""
from __future__ import annotations

import functools
from dataclasses import dataclass, replace
from typing import NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["AccelConfig", "PAPER_ACCEL", "ACCEL_ZOO", "HwVec", "HW_FIELDS",
           "HW_FEATURE_DIM", "as_hw", "stack_hw", "stack_hw_host", "hw_array",
           "hw_from_array", "accel_features", "accel_from_features"]

MB = float(2 ** 20)


@dataclass(frozen=True)
class AccelConfig:
    npe: int = 1024                  # PEs (paper §5.1)
    pe_lanes: int = 4                # vector MACs per PE (adaptation, DESIGN §4)
    freq_hz: float = 1e9             # 1 GHz
    bw_offchip: float = 8e9          # bytes/s (LPDDR-class edge device)
    bw_onchip: float = 40e9          # bytes/s (5:1 on:off, see DESIGN §4)
    buf_bytes: float = 64 * MB       # on-chip activation buffer
    bytes_per_elem: float = 1.0      # int8 tensors (edge inference)
    t_pass: float = 5e-6             # per-wave pipeline restart overhead (s)
    t_sync: float = 20e-6            # per-group off-chip sync/drain cost (s)
    stream_buf_bytes: float = 2 * MB  # act working set of an unfused layer
    name: str = "edge"               # zoo identity (not part of the hw vector)

    @property
    def peak_macs(self) -> float:
        return self.npe * self.pe_lanes * self.freq_hz

    def with_buffer_mb(self, mb: float) -> "AccelConfig":
        return replace(self, buf_bytes=mb * MB)


PAPER_ACCEL = AccelConfig()

# Named design points for hardware generalization (DESIGN.md §11).  "edge"
# is the paper-observed regime above; the others sweep compute, bandwidth,
# buffering and datatype across realistic device classes so the learned
# mapper sees genuinely different roofline/buffer trade-offs.
ACCEL_ZOO: dict[str, AccelConfig] = {
    "edge": PAPER_ACCEL,
    "nano": AccelConfig(
        name="nano", npe=256, pe_lanes=2, freq_hz=8e8, bw_offchip=4e9,
        bw_onchip=16e9, buf_bytes=8 * MB, bytes_per_elem=1.0, t_pass=5e-6,
        t_sync=30e-6, stream_buf_bytes=1 * MB),
    "mobile": AccelConfig(
        name="mobile", npe=2048, pe_lanes=4, freq_hz=1e9, bw_offchip=25.6e9,
        bw_onchip=128e9, buf_bytes=32 * MB, bytes_per_elem=1.0, t_pass=4e-6,
        t_sync=15e-6, stream_buf_bytes=2 * MB),
    "laptop": AccelConfig(
        name="laptop", npe=4096, pe_lanes=4, freq_hz=1.2e9, bw_offchip=68e9,
        bw_onchip=400e9, buf_bytes=96 * MB, bytes_per_elem=1.0, t_pass=3e-6,
        t_sync=12e-6, stream_buf_bytes=4 * MB),
    "datacenter": AccelConfig(
        name="datacenter", npe=16384, pe_lanes=8, freq_hz=1.5e9,
        bw_offchip=300e9, bw_onchip=2400e9, buf_bytes=192 * MB,
        bytes_per_elem=2.0, t_pass=2e-6, t_sync=10e-6,
        stream_buf_bytes=8 * MB),
}


# ---------------------------------------------------------------------------
# Traced hardware vector (DESIGN.md §11).
# ---------------------------------------------------------------------------

# Canonical field order of the raw hardware vector; slot i of a packed
# [..., HW_FEATURE_DIM] array is HW_FIELDS[i].
HW_FIELDS = ("npe", "pe_lanes", "freq_hz", "bw_offchip", "bw_onchip",
             "buf_bytes", "bytes_per_elem", "t_pass", "t_sync",
             "stream_buf_bytes")
HW_FEATURE_DIM = len(HW_FIELDS)

# Per-field log-range bounds for feature normalization: feature =
# log(x / lo) / log(hi / lo), so every realistic design point lands in
# [0, 1] and the map inverts exactly (accel_from_features).
_FEAT_LO = np.array([32, 1, 1e8, 1e8, 1e9, 0.25 * MB, 0.25, 1e-7, 1e-7,
                     0.0625 * MB], np.float64)
_FEAT_HI = np.array([2 ** 20, 64, 1e10, 1e13, 1e14, 16384 * MB, 8.0, 1e-3,
                     1e-2, 1024 * MB], np.float64)


class HwVec(NamedTuple):
    """``AccelConfig`` as a pytree of ``jnp`` scalars (or [C] vectors).

    Field names mirror :class:`AccelConfig`, so the cost model's arithmetic
    is agnostic to which it was handed; because it is a pytree, ``jit``
    traces through it and ``vmap``/``lax.scan`` run over stacked
    accelerators — the property the whole §11 condition-space rests on."""
    npe: jax.Array
    pe_lanes: jax.Array
    freq_hz: jax.Array
    bw_offchip: jax.Array
    bw_onchip: jax.Array
    buf_bytes: jax.Array
    bytes_per_elem: jax.Array
    t_pass: jax.Array
    t_sync: jax.Array
    stream_buf_bytes: jax.Array

    @property
    def peak_macs(self) -> jax.Array:
        return self.npe * self.pe_lanes * self.freq_hz


@functools.lru_cache(maxsize=256)
def _hw_of_cfg(cfg: AccelConfig) -> HwVec:
    """Cached AccelConfig -> HwVec (host constants -> f32 scalars)."""
    return HwVec(*(jnp.float32(getattr(cfg, f)) for f in HW_FIELDS))


def as_hw(hw) -> HwVec:
    """Normalize an accelerator descriptor to a traced :class:`HwVec`.

    Accepts an :class:`AccelConfig` (cached conversion), an ``HwVec``
    (passthrough, possibly mid-trace) or a raw ``[..., HW_FEATURE_DIM]``
    array in ``HW_FIELDS`` order."""
    if isinstance(hw, HwVec):
        return hw
    if isinstance(hw, AccelConfig):
        return _hw_of_cfg(hw)
    return hw_from_array(hw)


def hw_array(hw) -> jax.Array:
    """Raw ``[..., HW_FEATURE_DIM]`` f32 vector in ``HW_FIELDS`` order."""
    if isinstance(hw, AccelConfig):
        return jnp.asarray([float(getattr(hw, f)) for f in HW_FIELDS],
                           jnp.float32)
    if isinstance(hw, HwVec):
        return jnp.stack(list(hw), axis=-1).astype(jnp.float32)
    return jnp.asarray(hw, jnp.float32)


def hw_from_array(arr) -> HwVec:
    """Inverse of :func:`hw_array`; a leading batch axis becomes stacked
    per-condition leaves (the ``vmap``-over-hardware form)."""
    arr = jnp.asarray(arr, jnp.float32)
    return HwVec(*(arr[..., i] for i in range(HW_FEATURE_DIM)))


def stack_hw(hw, C: int) -> HwVec:
    """Per-condition ``HwVec`` with ``[C]`` leaves.

    ``hw`` may be one descriptor (broadcast to all C conditions), a
    sequence of C descriptors, an already-stacked ``HwVec``, or a raw
    ``[C, HW_FEATURE_DIM]`` array — the grid entry points
    (``cost_model.evaluate_grid``, ``gsampler_search_grid``,
    ``infer.dnnfuser_infer_batch``) all funnel through here."""
    if isinstance(hw, (list, tuple)) and not isinstance(hw, HwVec):
        if len(hw) != C:
            raise ValueError(f"got {len(hw)} accelerators for {C} conditions")
        return hw_from_array(jnp.stack([hw_array(h) for h in hw]))
    v = as_hw(hw)
    if jnp.ndim(v.npe) == 0:
        v = HwVec(*(jnp.broadcast_to(x, (C,)) for x in v))
    elif v.npe.shape[0] != C:
        raise ValueError(f"stacked HwVec has {v.npe.shape[0]} rows, "
                         f"expected {C}")
    return v


def stack_hw_host(hws) -> HwVec:
    """:func:`stack_hw` of a sequence of ``AccelConfig``s, built on the
    host: an ``HwVec`` of numpy ``[C]`` f32 columns, taken from one
    ``[C, HW_FEATURE_DIM]`` table in ``HW_FIELDS`` order."""
    table = np.array([[float(getattr(h, f)) for f in HW_FIELDS]
                      for h in hws], np.float32)
    return HwVec(*np.ascontiguousarray(table.T))


def accel_features(hw) -> jax.Array:
    """Normalized hardware condition features, ``[..., HW_FEATURE_DIM]``.

    Each raw field maps log-linearly onto [0, 1] over its ``_FEAT_LO`` /
    ``_FEAT_HI`` design range — the learned mapper's hw-condition input
    (DESIGN.md §11).  Works on an AccelConfig, HwVec (incl. stacked) or raw
    vector; invertible via :func:`accel_from_features`."""
    x = hw_array(hw)
    lo = jnp.asarray(_FEAT_LO, jnp.float32)
    span = jnp.asarray(np.log(_FEAT_HI / _FEAT_LO), jnp.float32)
    return (jnp.log(x / lo) / span).astype(jnp.float32)


def accel_from_features(feats, name: str = "decoded") -> AccelConfig:
    """Invert :func:`accel_features` back to an :class:`AccelConfig`.

    Integer fields (``npe``, ``pe_lanes``) are rounded; everything else
    round-trips to f32 precision."""
    f = np.asarray(jax.device_get(feats), np.float64)
    if f.shape != (HW_FEATURE_DIM,):
        raise ValueError(f"expected [{HW_FEATURE_DIM}] features, "
                         f"got shape {f.shape}")
    raw = _FEAT_LO * np.exp(f * np.log(_FEAT_HI / _FEAT_LO))
    kw = dict(zip(HW_FIELDS, raw))
    kw["npe"] = int(round(kw["npe"]))
    kw["pe_lanes"] = int(round(kw["pe_lanes"]))
    return AccelConfig(name=name, **kw)
