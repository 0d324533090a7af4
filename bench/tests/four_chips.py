"""The four-chip cell ``dt-unique-closed-4chip`` on four virtual CPU
devices, cut like ``conftest.small_spec``: a sound run, and one with the
exchange between chips left out (every chip's lanes answered with the
first chip's), each printed as one JSON line of its compared numbers,
whether they keep their limits, and the engine's replica count.

    XLA_FLAGS=--xla_force_host_platform_device_count=4 \\
        python3 bench/tests/four_chips.py

The device count has to be set before JAX starts, so the tests run this in
a process of its own.
"""
import json
import pathlib
import sys
import time

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

from conftest import small_spec  # noqa: E402


def one_run(seed: int) -> dict:
    from bench import harness, serve
    spec = small_spec("closed4")
    _, numbers, rec = serve.run(spec, seed, 1.5, False, time.perf_counter(),
                                harness.Compiles())
    ok, _ = harness.check_limits(numbers, spec.config["limits"])
    return {"correct": ok, "numbers": numbers, "attempted": rec.attempted,
            "replicas": rec.stats1["replicas"]["n_replicas"]}


def first_chip_everywhere(real):
    """``_fused_batch`` with the gather of the other chips' rows left out:
    each chip's block of lanes reads the first chip's block."""
    import numpy as np

    def broken(*args, **kw):
        out = {k: np.array(v) for k, v in real(*args, **kw).items()}
        for v in out.values():
            block = len(v) // 4
            v[block:] = np.concatenate([v[:block]] * 3)
        return out
    return broken


def main() -> None:
    import jax
    from bench import harness
    from repro.core import infer
    assert len(jax.devices()) == 4, jax.devices()
    harness.require_chips = lambda n: jax.devices()[:n]
    print(json.dumps(dict(one_run(2 ** 31 + 11), run="sound")), flush=True)
    infer._fused_batch = first_chip_everywhere(infer._fused_batch)
    print(json.dumps(dict(one_run(2 ** 31 + 11), run="no_exchange")),
          flush=True)


if __name__ == "__main__":
    main()
