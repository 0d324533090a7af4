"""The comparison that decides ``correct`` on small cells on the CPU: a
sound run passes its limits; the control (the reference one precision
below the configuration's, in the program's place) fails one of them; and
a run with the timed path broken underneath fails, once for each fault a
cell can have: a token altered where it is produced, an answer altered
where it is produced, and, in the four-chip cell, the exchange between
chips left out."""
from __future__ import annotations

import json
import os
import subprocess
import sys

import numpy as np
import pytest

from conftest import ROOT, small_spec


def within(numbers: dict, kind: str) -> bool:
    from bench import harness
    return harness.check_limits(numbers, small_spec(kind).config["limits"])[0]


@pytest.mark.parametrize("kind", ["closed", "open", "search"])
def test_a_sound_run_is_correct(cpu_run, kind):
    _, numbers, rec = cpu_run(kind)
    assert rec.attempted > 0 and within(numbers, kind), numbers


def test_the_control_fails(cpu_run):
    from bench import check
    spec = small_spec("closed")
    _, numbers, rec = cpu_run("closed")
    control = check.control_served(spec.reference, rec.sample, rec.params,
                                   rec.model, spec.config["limits"])
    assert within(numbers, "closed") and not within(control, "closed"), \
        control
    spec = small_spec("search")
    _, numbers, rec = cpu_run("search")
    control = check.check_search(spec.reference, rec.sample,
                                 spec.config["limits"],
                                 dt=spec.reference.BF16)
    assert not within(control, "search"), control


def _broken_rollout(monkeypatch, alter):
    from repro.core import infer
    real = infer._fused_batch

    def broken(*args, **kw):
        out = {k: np.array(v) for k, v in real(*args, **kw).items()}
        alter(out)
        return out
    monkeypatch.setattr(infer, "_fused_batch", broken)


def _alter_token(out):
    """Lane 0's first action moved to the far end of its range."""
    s = out["strategy"]
    s[0, 0] = 1 if s[0, 0] > 4 else 16


def _alter_answer(out):
    out["latency"][0] *= 1.001


@pytest.mark.parametrize("alter", [_alter_token, _alter_answer])
@pytest.mark.parametrize("kind", ["closed", "open"])
def test_a_broken_rollout_is_caught(cpu_run, monkeypatch, kind, alter):
    _broken_rollout(monkeypatch, alter)
    _, numbers, _ = cpu_run(kind)
    assert not within(numbers, kind), numbers


@pytest.mark.parametrize("field", ["strategies", "latency"])
def test_a_broken_search_is_caught(cpu_run, monkeypatch, field):
    from repro.core import gsampler
    real = gsampler._ga_grid

    def broken(*args, **kw):
        out = {k: np.array(v) for k, v in real(*args, **kw).items()}
        if field == "latency":
            out["latency"][:, 0] *= 1.001
        else:
            out["strategies"][:, 0, 0] = 0
        return out
    monkeypatch.setattr(gsampler, "_ga_grid", broken)
    _, numbers, _ = cpu_run("search")
    assert not within(numbers, "search"), numbers


def test_the_four_chip_cell_on_four_virtual_devices():
    """``dt-unique-closed-4chip`` cut to CPU size on four virtual devices,
    in a process of its own: four replicas serve it correctly, and leaving
    out the exchange between chips is caught."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = subprocess.run([sys.executable, "bench/tests/four_chips.py"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=900)
    assert p.returncode == 0, p.stderr[-4000:]
    runs = {r["run"]: r for r in (json.loads(ln) for ln in
                                  p.stdout.splitlines()
                                  if ln.startswith("{"))}
    sound, broken = runs["sound"], runs["no_exchange"]
    assert sound["replicas"] == broken["replicas"] == 4
    assert sound["attempted"] > 0 and sound["correct"], sound
    assert not broken["correct"], broken
