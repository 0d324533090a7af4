"""Host spans (``repro.obs``) and the device programs' name scopes.

 - a span tallies its host seconds and count into its owner's dict, and
   its metadata lands on the profiler's host events when a trace is taken;
 - the lowered rollout and G-Sampler programs carry the name scopes the
   benchmark's trace readers look for.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import DTConfig, GSamplerConfig, PAPER_ACCEL, dt_init
from repro.core import cost_model as cm
from repro.core import gsampler, infer
from repro.core.accel import accel_features, stack_hw
from repro.core.backend import backend_for
from repro.obs import span
from repro.workloads import tiny_cnn

MB = 2 ** 20


def test_span_tallies_seconds_and_count():
    totals: dict = {}
    for _ in range(3):
        with span("a", totals) as s:
            s.set_metadata(lanes=4)              # no trace: nothing built
    with span("b", totals):
        pass
    assert totals["a"]["count"] == 3 and totals["b"]["count"] == 1
    assert totals["a"]["seconds"] >= 0.0
    with pytest.raises(ValueError):
        with span("c", totals):
            raise ValueError("the span still closes")
    assert totals["c"]["count"] == 1


def test_span_metadata_shows_in_a_cpu_trace(tmp_path):
    from jax.profiler import ProfileData
    totals: dict = {}
    jax.profiler.start_trace(str(tmp_path))
    try:
        for tick in range(2):
            with span("engine.serve", totals) as s:
                s.set_metadata(tick=tick, lanes=3, nmax=32)
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(tmp_path, "**", "*.xplane.pb"),
                      recursive=True)
    events = [(e.name, dict(e.stats))
              for plane in ProfileData.from_file(path).planes
              if plane.name.startswith("/host:")
              for line in plane.lines for e in line.events
              if e.name == "engine.serve"]
    assert [m for _, m in events] == [{"tick": 0, "lanes": 3, "nmax": 32},
                                      {"tick": 1, "lanes": 3, "nmax": 32}]
    assert totals["engine.serve"]["count"] == 2


def _fused_batch_text() -> str:
    cfg = DTConfig(max_steps=8)
    params = dt_init(jax.random.PRNGKey(0), cfg)
    wl = cm.stack_workloads([cm.pack_workload(tiny_cnn(), PAPER_ACCEL, 8)])
    hwv = stack_hw([PAPER_ACCEL], 1)
    hwf = jnp.asarray(np.asarray(accel_features(hwv), np.float32))
    return infer._fused_batch.lower(
        params, cfg, wl, jnp.ones(1) * 16, jnp.ones(1) * 8 * MB, hwv, hwf,
        True, backend_for(cfg), True).as_text(debug_info=True)


def _ga_grid_text() -> str:
    wl = cm.stack_workloads([cm.pack_workload(tiny_cnn(), PAPER_ACCEL, 8)]
                            * 2)
    return gsampler._ga_grid.lower(
        jax.random.PRNGKey(0), wl, jnp.ones(2) * 8, jnp.ones(2) * 4 * MB,
        stack_hw([PAPER_ACCEL] * 2, 2),
        GSamplerConfig(population=8, generations=2), 2,
        "xla").as_text(debug_info=True)


@pytest.mark.parametrize("lower,scopes", [
    (_fused_batch_text, ("dt_decode", "guard", "env_step")),
    (_ga_grid_text, ("evaluate_grid", "repair", "repair/while/body/"
                     "evaluate_grid")),
], ids=["fused_batch", "ga_grid"])
def test_lowered_programs_carry_the_name_scopes(lower, scopes):
    text = lower()
    for scope in scopes:
        assert f"{scope}/" in text, scope
