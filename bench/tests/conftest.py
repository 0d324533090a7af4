"""Runs of the benchmark's cells on the CPU at a size a test can hold: the
harness's look for a chip is skipped, everything else runs."""
from __future__ import annotations

import os
import pathlib
import sys
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = pathlib.Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import pytest  # noqa: E402


CELLS = {"search": "gs-sweep64", "closed": "dt-unique-closed",
         "open": "dt-unique-closed", "closed4": "dt-unique-closed-4chip"}


def small_spec(kind: str):
    """A cell's spec (``harness.Spec``) with its configuration and mix cut
    to CPU size: the paper's widths with a 16-step trajectory on tiny_cnn
    for serving (``closed4``: the four-chip cell, 4 lanes a chip), a short
    search over two small networks for G-Sampler."""
    from bench import generate, harness
    spec = harness.Spec(CELLS[kind])
    cfg, mix = spec.config, spec.mix
    if kind == "search":
        cfg["search"]["gsampler"].update(population=8, generations=4)
        mix.update(networks=["tiny_cnn", "vgg16"], accels=["edge", "mobile"],
                   budgets_per_condition=2)
    else:
        cfg["model"]["max_steps"] = 16
        cfg["serving"].update(nmax_buckets=[8, 16], max_coalesce=4)
        if kind == "open":
            spec.mix = mix = generate.load("zipf-open")
            mix["grid"]["networks"] = ["tiny_cnn"]
            mix["rate_rps"] = 200
        else:
            mix.update(networks=["tiny_cnn"], callers=8 * spec.chips,
                       batches=[16, 32])
    return spec


@pytest.fixture
def cpu_run(monkeypatch):
    """``run(kind, seed)`` -> (end-to-end, compared numbers, record) of a
    small cell on the CPU."""
    import jax
    from bench import harness, search, serve
    monkeypatch.setattr(harness, "require_chips",
                        lambda n: jax.devices()[:n])

    def run(kind: str, seed: int = 2 ** 31 + 7, seconds: float = 1.5):
        driver = search if kind == "search" else serve
        return driver.run(small_spec(kind), seed, seconds, False,
                          time.perf_counter(), harness.Compiles())
    return run
