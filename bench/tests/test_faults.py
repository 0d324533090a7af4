"""The comparison that decides ``correct`` on small cells on the CPU: a
sound run passes its limits; the control (the reference one precision
below the configuration's, in the program's place) fails one of them; and
a run with the timed path broken underneath fails, once for each fault a
cell can have: a token altered where it is produced, an answer altered
where it is produced."""
from __future__ import annotations

import numpy as np
import pytest

from conftest import small_spec


def within(numbers: dict, kind: str) -> bool:
    from bench import harness
    return harness.check_limits(numbers, small_spec(kind).config["limits"])[0]


@pytest.mark.parametrize("kind", ["closed", "open", "search"])
def test_a_sound_run_is_correct(cpu_run, kind):
    _, numbers, rec = cpu_run(kind)
    assert rec.attempted > 0 and within(numbers, kind), numbers


def test_the_control_fails(cpu_run):
    from bench import check
    from bench.reference import BF16
    _, numbers, rec = cpu_run("closed")
    limits = small_spec("closed").config["limits"]
    control = check.control_served(rec.sample, rec.params, rec.model,
                                   limits)
    assert within(numbers, "closed") and not within(control, "closed"), \
        control
    _, numbers, rec = cpu_run("search")
    control = check.check_search(rec.sample,
                                 small_spec("search").config["limits"],
                                 dt=BF16)
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
