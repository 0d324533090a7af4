"""The harness at CPU speed, with no TPU topology: the files are found by
name, the traffic is a function of the seed, the work counts match a
hand count, the trace reduction matches recorded and hand-made traces,
the reference agrees with the program's own f64 oracle and model on the
CPU, and ``run.py`` refuses to run without a TPU."""
from __future__ import annotations

import json
import os
import pathlib
import re
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from conftest import ROOT

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")


def bench_json() -> dict:
    return json.loads((ROOT / "BENCHMARK.json").read_text())


def test_every_name_is_found():
    from bench import generate, harness
    b = bench_json()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    for c in b["configs"]:
        assert (ROOT / c["file"]).is_file() and NAME.match(c["name"])
        assert json.loads((ROOT / c["file"]).read_text())["name"] == c["name"]
    for m in b["per_layer"]:
        assert (ROOT / "bench" / "metrics" / f"{m['name']}.py").is_file()
    e2e = {m["name"] for m in b["end_to_end"]}
    for w in b["workloads"]:
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        generate.load(w["traffic"])
        spec = harness.Spec(w["name"])
        mine = {m["name"] for m in spec.end_to_end()}
        assert "setup_s" in mine and len(mine) >= 2
        layer = spec.per_layer()
        assert layer and all(m["moves"] in mine & e2e for m in layer)
    assert {m["name"] for m in b["per_layer"]} <= {
        p.stem for p in (ROOT / "bench" / "metrics").glob("*.py")}


def test_bounds_and_units_keep_to_the_contract():
    b = bench_json()
    for m in b["end_to_end"] + b["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    assert 1 <= b["run_seconds"] <= 51
    four = sum(w["chips"] == 4 for w in b["workloads"])
    assert four <= max(1, len(b["workloads"]) // 2)


@pytest.mark.parametrize("cell", [w["name"] for w in bench_json()
                                  ["workloads"] if w["config"]
                                  == "dnnfuser-dt"])
def test_serving_settings_are_the_configurations_per_chip(cell):
    from bench import harness
    spec = harness.Spec(cell)
    own = spec.config["serving"]
    got = spec.serving()
    if spec.chips == 1:
        assert got == own and got["replicas"] is None
    else:
        assert spec.chips == 4
        assert got["replicas"] == 4 and got["max_coalesce"] == 64
        assert {k: v for k, v in got.items()
                if k not in ("replicas", "max_coalesce")} == {
            k: v for k, v in own.items()
            if k not in ("replicas", "max_coalesce")}


def test_the_reference_is_the_one_the_configuration_names(monkeypatch,
                                                          tmp_path):
    """Without ``reference`` a configuration gets ``bench/reference.py``;
    one that names a copy under ``bench/tests`` gets that copy."""
    import shutil
    from bench import harness
    for cell in ("dt-unique-closed", "gs-sweep64"):
        ref = harness.Spec(cell).reference
        assert pathlib.Path(ref.__file__) == ROOT / "bench" / "reference.py"
        assert not hasattr(ref, "MARK")
    b = bench_json()
    cfg = json.loads((ROOT / "bench/configs/dnnfuser-dt.json").read_text())
    cfg["reference"] = "bench/tests/reference_copy.py"
    (tmp_path / "bench/configs").mkdir(parents=True)
    (tmp_path / "bench/tests").mkdir()
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(b))
    (tmp_path / "bench/configs/dnnfuser-dt.json").write_text(
        json.dumps(cfg))
    shutil.copy(ROOT / cfg["reference"], tmp_path / cfg["reference"])
    monkeypatch.setattr(harness, "ROOT", tmp_path)
    monkeypatch.setattr(harness, "BENCH", tmp_path / "bench")
    ref = harness.Spec("dt-unique-closed").reference
    assert ref.MARK == cfg["reference"] and ref.legal is not None
    with pytest.raises(SystemExit):
        harness.load_reference("BENCHMARK.json")


def test_same_seed_same_stream_and_fixed_popularity():
    from bench import generate
    mix = generate.load("zipf-open")
    a = generate.open_stream(mix, np.random.default_rng(11), 5.0)
    b = generate.open_stream(mix, np.random.default_rng(11), 5.0)
    c = generate.open_stream(mix, np.random.default_rng(12), 5.0)
    assert a == b and a != c

    def top(stream):
        seen = [cond for _, cond, unseen in stream if not unseen]
        return max(set(seen), key=seen.count)
    mix = dict(mix, rate_rps=4000)
    assert (top(generate.open_stream(mix, np.random.default_rng(1), 5.0))
            == top(generate.open_stream(mix, np.random.default_rng(2), 5.0)))

    closed = generate.load("unique-closed")
    g1 = generate.closed_conditions(closed, np.random.default_rng(5))
    g2 = generate.closed_conditions(closed, np.random.default_rng(5))
    block = len(closed["networks"]) * len(closed["accels"]) * len(
        closed["batches"])
    first = [next(g1) for _ in range(block)]
    assert first == [next(g2) for _ in range(block)]
    assert len({(c.network, c.accel, c.batch) for c in first}) == block

    calls = generate.search_calls(generate.load("sweep64"),
                                  np.random.default_rng(3))
    assert len(next(calls)) == 64


def test_work_matches_a_hand_count_for_the_paper_dt():
    from bench import work
    cfg = dict(n_blocks=3, n_heads=2, d_model=128, d_ff=512, max_steps=64,
               hw_dim=10)
    # one token through one block: q, k, v, o (4 x 128 x 128 MACs), the MLP
    # (2 x 128 x 512 MACs) and attention over `a` tokens (2 x 128 x a MACs)
    block = lambda a: 2 * (4 * 128 * 128 + 2 * 128 * 512 + 2 * 128 * a)
    head = 2 * 128
    step0 = (2 * 128 * 11 + 2 * 128 * 8                  # r0 with hw, s0
             + 3 * (block(1) + block(2)) + head)
    step1 = (2 * 128 * 1 + 2 * 128 * 11 + 2 * 128 * 8    # a0, r1, s1
             + 3 * (block(3) + block(4) + block(5)) + head)
    assert work.dt_step_flops(cfg, 0) == step0 == 2369024
    assert work.dt_step_flops(cfg, 1) == step1
    assert work.dt_episode_flops(cfg, 1) == step0 + step1
    assert work.eval_bytes(50) == 4 * 51 + 12
    assert work.eval_ops(50) == (35 + 9) * 50


HAND_TRACE = {
    "devices": {"/device:TPU:0": {
        "modules": [["jit__fused_batch(3)", 1000, 400],
                    ["jit__fused_batch(3)", 2000, 300]],
        "ops": [["fusion.1", 1000, 200], ["while.2", 1100, 300],
                ["copy.5", 2000, 300]]}},
    "host": [["window", 900, 1700], ["pump", 1500, 400],
             ["generate", 2400, 150]],
}


def test_trace_reduction_on_a_hand_made_trace():
    from bench import trace
    r = trace.reduce(HAND_TRACE)
    assert r["window_s"] == pytest.approx(1700e-9)
    assert r["busy_s"] == pytest.approx(700e-9)
    assert r["idle_share"] == pytest.approx(1000 / 1700)
    assert trace.module_time(r, "jit__fused_batch") == pytest.approx(
        (700e-9, 2))
    assert dict(r["idle_gaps"]) == pytest.approx(
        {"pump": 600e-9, "generate": 300e-9, "other": 100e-9})
    assert dict(r["device_ops"]) == pytest.approx(
        {"while": 300e-9, "copy": 300e-9, "fusion": 200e-9})


TWO_CHIPS = {
    "devices": {f"/device:TPU:{i}": {
        "modules": [["jit__fused_batch(7)", 1000, 400],
                    ["jit__fused_batch(7)", 2000, 300],
                    ["jit__other(1)", 2500, 100]],
        "ops": [["%while.3", 1000, 400], ["%all-reduce.2", 1100, 20 + i],
                ["%all-reduce.2", 1300, 30], ["%fusion.1", 2000, 100],
                ["%all-reduce-start.4", 2150, 10 * (i + 1)],
                ["%all-reduce-done.4", 2200, 5],
                ["%all-gather.1", 2550, 40],
                ["%all-reduce.9", 2700, 50]]}
        for i in range(2)},
    "host": [["window", 900, 1900]],
}


def test_collective_time_per_module_on_a_two_chip_trace():
    """The all-reduce and all-gather ops inside each module's calls,
    averaged over the two device planes; ops outside every call (at 2700,
    after the last call ends) are not counted."""
    from bench import harness, trace
    r = trace.reduce(TWO_CHIPS)
    per_dev = [20 + i + 30 + 10 * (i + 1) + 5 for i in range(2)]
    assert r["collectives"] == pytest.approx({
        "jit__fused_batch": sum(per_dev) / 2 * 1e-9, "jit__other": 40e-9})
    assert trace.module_time(r, "jit__fused_batch") == pytest.approx(
        (700e-9, 2))
    assert r["busy_s"] == pytest.approx(610e-9)
    read = harness.load_module(ROOT / "bench/metrics/allreduce_ms.x4.py").read
    assert read(SimpleNamespace(trace=r)) == pytest.approx(
        sum(per_dev) / 2 / 2 * 1e-6)
    assert read(SimpleNamespace(trace=trace.reduce(HAND_TRACE))) is None
    # the one-chip cell's readers serve the four-chip cell as they are
    rec = SimpleNamespace(trace=r)
    rollout = harness.load_module(ROOT / "bench/metrics/rollout_ms.unique.py")
    idle = harness.load_module(ROOT / "bench/metrics/device_idle.unique.py")
    assert rollout.read(rec) == pytest.approx(350e-6)
    assert idle.read(rec) == pytest.approx(100 * (1 - 610 / 1900))


@pytest.mark.parametrize("name", sorted(
    p.name for p in (ROOT / "bench" / "tests").glob("trace_*.json")))
def test_trace_reduction_on_a_recorded_chip_trace(name):
    """A slice of a trace recorded on a TPU v5e, reduced to the numbers
    stored beside it."""
    from bench import trace
    rec = json.loads((ROOT / "bench" / "tests" / name).read_text())
    r = trace.reduce(rec["events"])
    for key in ("window_s", "busy_s", "idle_share"):
        assert r[key] == pytest.approx(rec["expected"][key], rel=1e-9)
    for mod, (secs, calls) in rec["expected"]["modules"].items():
        assert r["modules"][mod] == pytest.approx([secs, calls], rel=1e-9)
    assert sum(v for _, v in r["idle_gaps"]) == pytest.approx(
        r["window_s"] - r["busy_s"], rel=1e-9)
    assert r["collectives"] == {}       # one chip: no collective


def test_run_exits_non_zero_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "dt-unique-closed", "--seed", "1", "--seconds", "1"],
                       cwd=ROOT, env=env, capture_output=True, text=True,
                       timeout=120)
    assert p.returncode != 0
    assert "no TPU" in p.stderr and not p.stdout.strip()


def test_reference_costs_match_the_programs_f64_oracle():
    """A second witness: the repository's own loop-based f64 oracle."""
    from bench import reference as R
    from repro import ACCEL_ZOO
    from repro.core import cost_model as cm, ref_model
    from repro.workloads import get_workload
    rng = np.random.default_rng(0)
    for net in ("resnet50", "mobilenet_v2", "vgg16"):
        w = get_workload(net)
        for acc in (ACCEL_ZOO["edge"], ACCEL_ZOO["datacenter"]):
            arr = R.layer_arrays(w, acc.bytes_per_elem)
            wl = w.arrays(64, bytes_per_elem=acc.bytes_per_elem)
            S = np.stack([cm.random_strategy(rng, w.n, w.n + 1, 32)
                          for _ in range(12)])
            out = R.evaluate(arr, S, 32, acc)
            for k, s in enumerate(S):
                full = np.full(64, R.SYNC)
                full[: w.n + 1] = s
                r = ref_model.evaluate_ref(wl, full, 32, 1e12, acc)
                assert out["latency"][k] == pytest.approx(r["latency"],
                                                          rel=1e-12)
                assert out["peak"][k] == pytest.approx(r["peak_mem"],
                                                       rel=1e-12)
                assert out["n_groups"][k] == r["n_groups"]
            assert R.baseline(arr, 32, acc) == pytest.approx(
                ref_model.baseline_ref(wl, 32, acc), rel=1e-12)


def test_reference_environment_and_model_match_the_program_on_cpu():
    """On the CPU the program's f32 environment, accelerator features and
    decision transformer agree with the reference to f32 round-off."""
    import jax.numpy as jnp
    from bench import harness, reference as R
    from repro import ACCEL_ZOO, DTConfig
    from repro.core.model import dt_apply
    from repro.core import FusionEnv
    from repro.core.accel import accel_features
    from repro.core.env import encode_action
    from repro.workloads import get_workload
    w, acc = get_workload("resnet18"), ACCEL_ZOO["mobile"]
    env = FusionEnv(w, acc, batch=32, budget_bytes=20 * R.MB, nmax=32)
    strategy = np.array([8] + [4, -1, 16, 2] * 4 + [-1, 32], np.int64)
    decorated = env.decorate(np.concatenate(
        [strategy, np.full(32 - len(strategy), -1)]))
    rtg, states = R.observations(R.layer_arrays(w, 1.0), strategy, 32,
                                 20 * R.MB, acc)
    np.testing.assert_allclose(rtg, decorated["rtg"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(states, decorated["states"], rtol=1e-5,
                               atol=1e-6)
    np.testing.assert_allclose(R.hw_features(acc), accel_features(acc),
                               rtol=1e-6)

    model = dict(n_blocks=3, n_heads=2, d_model=128, d_ff=512, max_steps=32,
                 hw_dim=10)
    params = harness.dt_weights(model, 3)
    T = 32
    args = (jnp.asarray(np.pad(rtg, (0, T - len(rtg)))[None], jnp.float32),
            jnp.asarray(np.pad(states, ((0, T - len(states)), (0, 0)))[None],
                        jnp.float32),
            jnp.asarray(np.pad(encode_action(strategy, 32),
                               (0, T - len(strategy)))[None], jnp.float32),
            jnp.asarray(R.hw_features(acc)[None], jnp.float32))
    ours = R.dt_forward(params, *args, n_heads=2)
    theirs = dt_apply(params, DTConfig(max_steps=T, hw_dim=10), *args[:3],
                      hw=args[3])
    np.testing.assert_allclose(ours, theirs, rtol=1e-4, atol=1e-5)


def test_seeds_reorder_one_mapper():
    """Two seeds' weights differ leaf by leaf and compute the same
    decision transformer to f32 round-off, so every seed gives the cell
    the same work."""
    import jax
    import jax.numpy as jnp
    from bench import harness
    from repro import DTConfig
    from repro.core.model import dt_apply
    model = dict(n_blocks=3, n_heads=2, d_model=128, d_ff=512, max_steps=16,
                 hw_dim=10)
    a = harness.dt_weights(model, 2 ** 31 + 7)
    b = harness.dt_weights(model, 5)
    la, lb = jax.tree_util.tree_leaves(a), jax.tree_util.tree_leaves(b)
    assert [x.shape for x in la] == [x.shape for x in lb]
    # every leaf but the head's constant bias is reordered
    assert sum(not np.array_equal(x, y) for x, y in zip(la, lb)) == len(la) - 1
    rng = np.random.default_rng(0)
    args = [jnp.asarray(rng.normal(size=s), jnp.float32)
            for s in ((4, 16), (4, 16, 8), (4, 16), (4, 10))]
    cfg = DTConfig(max_steps=16, hw_dim=10)
    with jax.default_matmul_precision("highest"):
        ya = dt_apply(a, cfg, *args[:3], hw=args[3])
        yb = dt_apply(b, cfg, *args[:3], hw=args[3])
    assert float(jnp.std(ya)) > 0.05
    np.testing.assert_allclose(ya, yb, rtol=1e-4, atol=1e-5)
