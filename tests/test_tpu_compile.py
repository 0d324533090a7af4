"""Compile the mapper's device programs for a described TPU v5e chip.

Nothing runs: each test lowers and compiles one program at its real size
for one chip of a ``v5e:2x2`` topology that is described, not attached, so
what the TPU compiler would refuse (Mosaic block tiling, unsupported
primitives in a kernel, programs that do not fit) fails here without a
chip.  The topology is described inside a module-scoped fixture, never at
import, and all of these tests stay in this one file: only the process that
runs them loads the TPU compiler.  The persistent compilation cache is off
around the compiles (an entry written for a described chip cannot be read
back without one).
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro import optim
from repro.core import cost_model as cm
from repro.core import gsampler, infer
from repro.core.accel import (ACCEL_ZOO, HW_FEATURE_DIM, PAPER_ACCEL, HwVec,
                              stack_hw)
from repro.core.gsampler import GSamplerConfig
from repro.core.backend import backend_for
from repro.core.env import STATE_DIM
from repro.core.model import DTConfig, dt_init, dt_loss
from repro.core.train import make_train_step
from repro.kernels import fusion_eval
from repro.workloads import get_workload, resnet50

CFG = DTConfig(hw_dim=HW_FEATURE_DIM)


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # any failure: no TPU compiler to describe it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.experimental.compilation_cache import compilation_cache
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _param_shapes():
    return jax.eval_shape(lambda k: dt_init(k, CFG), jax.random.PRNGKey(0))


def _sds(tree, sharding):
    return jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sharding),
        tree)


@pytest.mark.parametrize("C,POP,P", [(4, 256, 64), (1, 8, 8)])
def test_fusion_eval_kernel_compiles(one_chip, C, POP, P):
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32, sharding=one_chip)
    wls = {k: f32(C, P) for k in ("A", "W", "F", "OE", "UC")}
    wls.update(SKIP=i32(C, P), n=i32(C), BPE=f32(C))
    compiled = fusion_eval._fusion_eval_grid_jit.lower(
        f32(C, POP, P), wls, f32(C), f32(C), f32(C, HW_FEATURE_DIM),
        bp=fusion_eval._block_size(POP), interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_gsampler_pallas_program_compiles(one_chip, monkeypatch):
    """G-Sampler's grid program with the kernel as its evaluator, inside
    the jitted body: 6 conditions at nmax 64, population 32.  The kernel's
    lowering mode is chosen from the platform, which is the CPU here, so
    the test forces the compiled one."""
    call_grid = fusion_eval._call_grid
    monkeypatch.setattr(
        fusion_eval, "_call_grid",
        lambda *a, interpret, **k: call_grid(*a, interpret=False, **k))
    accels = [ACCEL_ZOO["edge"], ACCEL_ZOO["mobile"]] * 3
    wls = cm.stack_workloads([cm.pack_workload(resnet50(), a, 64)
                              for a in accels])
    C = len(accels)
    compiled = gsampler._ga_grid.lower(
        _sds(jax.random.PRNGKey(0), one_chip), _sds(wls, one_chip),
        *_sds((jnp.zeros(C), jnp.zeros(C), stack_hw(accels, C)), one_chip),
        GSamplerConfig(population=32, generations=8, elite=4,
                       repair_tries=4), 4, "pallas").compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_engine_rollout_compiles(one_chip):
    """The engine's fused rollout: DTConfig() width, nmax 64, a 16-lane
    tick of stacked workloads."""
    lanes, nmax = 16, 64
    params = _sds(_param_shapes(), one_chip)
    packed = cm.pack_workload(resnet50(), PAPER_ACCEL, nmax)
    wl = {k: jax.ShapeDtypeStruct((lanes,) + v.shape, v.dtype,
                                  sharding=one_chip)
          for k, v in packed.items()}
    vec = jax.ShapeDtypeStruct((lanes,), jnp.float32, sharding=one_chip)
    hw = HwVec(*([vec] * HW_FEATURE_DIM))
    hwf = jax.ShapeDtypeStruct((lanes, HW_FEATURE_DIM), jnp.float32,
                               sharding=one_chip)
    compiled = infer._fused_batch.lower(
        params, CFG, wl, vec, vec, hw, hwf, True, backend_for(CFG),
        True).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2 ** 30


def test_engine_rollout_compiles_at_128_steps(one_chip):
    """The rollout of a 128-step mapper in its nmax-128 bucket: a 16-lane
    tick of DeepSeek-V3 prefill chains (126 positions)."""
    lanes, nmax = 16, 128
    cfg = DTConfig(hw_dim=HW_FEATURE_DIM, max_steps=nmax)
    params = _sds(jax.eval_shape(lambda k: dt_init(k, cfg),
                                 jax.random.PRNGKey(0)), one_chip)
    packed = cm.pack_workload(get_workload("deepseek_v3_prefill_32k"),
                              ACCEL_ZOO["datacenter"], nmax)
    wl = {k: jax.ShapeDtypeStruct((lanes,) + v.shape, v.dtype,
                                  sharding=one_chip)
          for k, v in packed.items()}
    vec = jax.ShapeDtypeStruct((lanes,), jnp.float32, sharding=one_chip)
    hw = HwVec(*([vec] * HW_FEATURE_DIM))
    hwf = jax.ShapeDtypeStruct((lanes, HW_FEATURE_DIM), jnp.float32,
                               sharding=one_chip)
    compiled = infer._fused_batch.lower(
        params, cfg, wl, vec, vec, hw, hwf, True, backend_for(cfg),
        True).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2 ** 30


def test_train_step_compiles(one_chip):
    """One imitation-training step at DTConfig() width, batch 64."""
    batch_size, T = 64, CFG.max_steps
    tx = optim.adamw(optim.cosine_with_warmup(3e-4, 100, 3000),
                     weight_decay=1e-4, max_grad_norm=1.0)
    shapes = _param_shapes()
    params = _sds(shapes, one_chip)
    opt_state = _sds(jax.eval_shape(tx.init, shapes), one_chip)
    f32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)
    batch = {"rtg": f32(batch_size, T), "states": f32(batch_size, T,
                                                      STATE_DIM),
             "actions": f32(batch_size, T), "mask": f32(batch_size, T),
             "t0": jax.ShapeDtypeStruct((batch_size,), jnp.int32,
                                        sharding=one_chip),
             "hw": f32(batch_size, HW_FEATURE_DIM)}
    step = make_train_step(lambda p, b: dt_loss(p, CFG, b), tx)
    compiled = step.lower(params, opt_state, batch).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 16 * 2 ** 30
