"""The DeepSeek-V3 prefill configuration and the two cells added beside
it, at CPU speed: the configuration's own reference builds the program's
chains at the published widths, both traffic mixes load and name what the
program serves, the configuration's serving settings and reference
resolve, the new counter's reader reads the window's deltas and nothing
from a program without the counter, and a small run of the DeepSeek-V3
cell on the CPU is correct."""
from __future__ import annotations

import importlib.util
import time
from types import SimpleNamespace

import numpy as np
import pytest

from conftest import ROOT

DSV3 = ["deepseek_v3_prefill_4k", "deepseek_v3_prefill_16k",
        "deepseek_v3_prefill_32k"]


def reader(name: str):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        "m_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


@pytest.mark.parametrize("net", DSV3)
def test_reference_builds_the_programs_chain_at_published_widths(net):
    from bench import harness
    from repro.workloads import get_workload
    R = harness.load_reference("bench/reference_dsv3.py")
    w = get_workload(net)
    assert w.n == 125
    for bpe in (1.0, 2.0):
        ref = R.layer_arrays(w, bpe)
        got = w.arrays(128, bytes_per_elem=bpe)
        n1 = w.n + 1
        assert ref["n"] == w.n
        for k in ("A", "W", "F", "OE", "UC", "SKIP", "SHAPE6"):
            np.testing.assert_array_equal(ref[k], got[k][:n1], err_msg=k)


def test_reference_defers_to_the_plain_reference_for_other_networks():
    from bench import harness, reference
    from repro.workloads import get_workload
    R = harness.load_reference("bench/reference_dsv3.py")
    w = get_workload("tiny_cnn")
    a, b = R.layer_arrays(w, 1.0), reference.layer_arrays(w, 1.0)
    for k in ("A", "W", "F", "OE", "UC", "SKIP", "SHAPE6"):
        np.testing.assert_array_equal(a[k], b[k], err_msg=k)
    assert R.evaluate is reference.evaluate and R.SYNC == reference.SYNC


@pytest.mark.parametrize("traffic", ["unique-short", "dsv3-prefill-closed"])
def test_traffic_loads_and_names_what_the_program_serves(traffic):
    from bench import generate
    from repro import ACCEL_ZOO
    from repro.workloads import get_workload
    mix = generate.load(traffic)
    assert mix["loop"] == "closed" and mix["callers"] == 64
    gen = generate.closed_conditions(mix, np.random.default_rng(2 ** 31 + 9))
    conds = [next(gen) for _ in range(len(mix["networks"])
                                      * len(mix["accels"])
                                      * len(mix["batches"]))]
    lo, hi = mix["budget_mb"]
    for c in conds:
        get_workload(c.network)
        assert c.accel in ACCEL_ZOO
        assert lo * generate.MB <= c.budget_bytes <= hi * generate.MB
    # every (network, accelerator, batch) once in each block
    assert len({(c.network, c.accel, c.batch) for c in conds}) == len(conds)


def test_the_configurations_serving_and_reference_resolve():
    from bench import harness
    spec = harness.Spec("dsv3-prefill-closed")
    sc = spec.serving()
    assert sc == spec.config["serving"] and sc["replicas"] is None
    assert sc["nmax_buckets"] == [8, 16, 32, 64, 128]
    assert spec.config["model"]["max_steps"] == 128
    assert spec.reference.__file__ == str(ROOT / "bench" /
                                          "reference_dsv3.py")
    assert spec.config["limits"] == harness.Spec(
        "dt-unique-closed").config["limits"]
    assert {m["name"] for m in spec.end_to_end()} == {"setup_s", "map_rps"}
    assert "guard_syncs.dsv3" in {m["name"] for m in spec.per_layer()}
    short = harness.Spec("dt-unique-short")
    assert short.reference.__file__ == str(ROOT / "bench" / "reference.py")
    assert "guard_syncs.dsv3" not in {m["name"] for m in short.per_layer()}


def test_guard_syncs_reader_reads_the_window_and_nothing_without_it():
    read = reader("guard_syncs.dsv3")
    rec = SimpleNamespace(
        stats0={"guard_syncs": 10, "rollout_steps": 400},
        stats1={"guard_syncs": 60, "rollout_steps": 600})
    assert read(rec) == pytest.approx(0.25)
    old = SimpleNamespace(stats0={"rollout_steps": 400},
                          stats1={"rollout_steps": 600})
    assert read(old) is None


def test_a_small_run_of_the_deepseek_cell_is_correct(monkeypatch):
    """The cell on the CPU with fewer callers and lanes: every served
    strategy is the reference's, and the guard forced SYNCs."""
    import jax
    from bench import harness, serve
    monkeypatch.setattr(harness, "require_chips",
                        lambda n: jax.devices()[:n])
    spec = harness.Spec("dsv3-prefill-closed")
    spec.config["serving"].update(max_coalesce=4)
    spec.mix.update(callers=8, batches=[4, 8])
    e2e, numbers, rec = serve.run(spec, 2 ** 31 + 12345, 1.5, False,
                                  time.perf_counter(), harness.Compiles())
    ok, _ = harness.check_limits(numbers, spec.config["limits"])
    assert ok, numbers
    assert e2e["map_rps"] > 0 and rec.attempted > 0
    assert reader("guard_syncs.dsv3")(rec) > 0
