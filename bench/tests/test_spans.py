"""``bench/spans.py`` and the readers of the program's spans, scopes and
counters, on a hand-made trace, on a slice of a trace recorded on a TPU
v5e, and on a program that keeps none of them (each reader then finds
nothing and returns None)."""
from __future__ import annotations

import importlib.util
import json
from types import SimpleNamespace

import pytest

from conftest import ROOT

FUSED = "jit(_fused_batch)/jit(main)/while/body"
HAND = {
    "host": [["window", 1000, 1000, {}],
             ["scheduler.pump", 1100, 800, {}],
             ["scheduler.flush", 1120, 760, {"nmax": 64, "requests": 4,
                                             "reason": "width",
                                             "wait_s": 0.02}],
             ["engine.serve", 1150, 700, {"tick": 3, "lanes": 2,
                                          "nmax": 64}],
             ["engine.pack", 1160, 40, {}],
             ["engine.dispatch", 1200, 50, {}],
             ["engine.wait", 1250, 450, {}],
             ["engine.unpack", 1700, 140, {}],
             ["generate", 1920, 30, {}],
             ["gsampler.unpack", 1960, 20, {"repair_rounds": 9,
                                            "generations": 5}]],
    "devices": {"/device:TPU:0": {
        "modules": [["jit__fused_batch(3)", 1220, 460]],
        "ops": [["while.1", 1230, 370, f"{FUSED}/guard/while"],
                ["fusion.1", 1300, 100, f"{FUSED}/guard/while/body/"
                                        "evaluate/fusion"],
                ["fusion.2", 1450, 50, f"{FUSED}/dt_decode/dot_general"],
                ["fusion.3", 1610, 60, f"{FUSED}/env_step/add"]]}},
}


def reader(name: str):
    path = ROOT / "bench" / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("m_" + name.replace(
        ".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_self_time_of_nested_ops_is_a_while_less_its_body():
    from bench import spans
    own = spans._self_times(HAND["devices"]["/device:TPU:0"]["ops"])
    assert own == [220.0, 100.0, 50.0, 60.0]
    # per call of the one whole jit__fused_batch call, in ms
    assert spans.scope_self_ms(HAND, "jit__fused_batch", "guard") == \
        pytest.approx(320e-6)
    assert spans.scope_self_ms(HAND, "jit__fused_batch", "dt_decode") == \
        pytest.approx(50e-6)
    assert spans.scope_self_ms(HAND, "jit__fused_batch", "repair") is None
    assert spans.scope_self_ms(HAND, "jit__ga_grid", "guard") is None


def test_idle_goes_to_the_innermost_open_span():
    from bench import spans
    idle = spans.idle_by_span(HAND)
    assert idle == pytest.approx({
        None: 150e-9, "scheduler.pump": 40e-9, "scheduler.flush": 60e-9,
        "engine.serve": 20e-9,
        "engine.pack": 40e-9, "engine.dispatch": 30e-9,
        "engine.wait": 40e-9, "engine.unpack": 140e-9, "generate": 30e-9,
        "gsampler.unpack": 20e-9})
    assert sum(idle.values()) == pytest.approx(570e-9)
    assert spans.module_inside(HAND, "jit__fused_batch",
                               ("engine.wait", "engine.dispatch")) == 1.0
    assert spans.module_inside(HAND, "jit__fused_batch",
                               ("engine.wait",)) == pytest.approx(430 / 460)


def test_trace_readers_on_the_hand_made_trace():
    rec = SimpleNamespace(span_events=HAND, trace={"window_s": 1000e-9})
    assert reader("idle_host.unique")(rec) == pytest.approx(33.0)
    assert reader("guard_ms.unique")(rec) == pytest.approx(320e-6)
    assert reader("decode_ms.unique")(rec) == pytest.approx(50e-6)
    assert reader("queue_wait_ms.unique")(rec) == pytest.approx(5.0)
    assert reader("repair_rounds.sweep")(rec) == pytest.approx(9 / 5)
    assert reader("search_host_ms.sweep")(rec) == pytest.approx(20e-6)
    assert reader("eval_ms.sweep")(rec) is None


def _stats(serve, wait, pump, calls, iters, steps, warm=None) -> dict:
    spans = {"engine.serve": {"seconds": serve, "count": calls},
             "engine.wait": {"seconds": wait, "count": calls}}
    if warm is not None:
        spans["engine.warmup"] = {"seconds": warm, "count": 1}
    return {"device_calls": calls, "guard_iters": iters,
            "rollout_steps": steps, "spans": spans,
            "scheduler": {"spans": {"scheduler.pump": {"seconds": pump,
                                                       "count": 9}}}}


def test_counter_readers_read_the_window_deltas():
    rec = SimpleNamespace(
        stats0=_stats(1.0, 0.5, 1.5, 10, 100, 400, warm=20.0),
        stats1=_stats(3.0, 1.5, 4.5, 110, 600, 2400, warm=20.0))
    assert reader("engine_host_ms.unique")(rec) == pytest.approx(10.0)
    assert reader("engine_wait_ms.unique")(rec) == pytest.approx(10.0)
    assert reader("sched_self_ms.unique")(rec) == pytest.approx(10.0)
    assert reader("guard_iters.unique")(rec) == pytest.approx(0.25)
    assert reader("warmup_s.unique")(rec) == 20.0


COUNTERS = ["engine_host_ms.unique", "engine_wait_ms.unique",
            "sched_self_ms.unique", "guard_iters.unique", "warmup_s.unique"]
TRACED = ["queue_wait_ms.unique", "decode_ms.unique", "guard_ms.unique",
          "idle_host.unique", "eval_ms.sweep", "repair_rounds.sweep",
          "search_host_ms.sweep"]


@pytest.mark.parametrize("name", COUNTERS + TRACED)
def test_a_program_without_spans_gives_nothing(name):
    """The readers on a program that keeps neither span tallies nor guard
    counters, and whose trace holds only the benchmark's own spans and
    unscoped ops: each returns None and raises nothing."""
    old = {"device_calls": 5, "coalesce_width_hist": {4: 5},
           "scheduler": {"flushes": {"width": 5}}}
    bare = {"host": [h for h in HAND["host"] if h[0] in ("window",
                                                        "generate")],
            "devices": {d: {"modules": v["modules"],
                            "ops": [o[:3] + [""] for o in v["ops"]]}
                        for d, v in HAND["devices"].items()}}
    rec = SimpleNamespace(stats0=old, stats1=dict(old, device_calls=9),
                          span_events=bare, trace={"window_s": 1e-6})
    assert reader(name)(rec) is None


def recorded() -> dict:
    rec = json.loads((ROOT / "bench" / "tests"
                      / "spans_dt_unique.json").read_text())
    for dev in rec["events"]["devices"].values():
        for op in dev["ops"]:
            op[3] = rec["scopes"][op[3]]
    return rec


def test_spans_on_a_recorded_chip_trace():
    """One fused-rollout call recorded on a TPU v5e with the engine's spans
    around it, reduced to the numbers stored beside it."""
    from bench import spans, trace
    rec = recorded()
    ev, want = rec["events"], rec["expected"]
    idle = spans.idle_by_span(ev)
    assert {str(k): v for k, v in idle.items()} == pytest.approx(
        want["idle_by_span"], rel=1e-9)
    r = trace.reduce({"devices": {d: {k: [o[:3] for o in v[k]] for k in v}
                                  for d, v in ev["devices"].items()},
                      "host": [h[:3] for h in ev["host"]]})
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"],
                                               rel=1e-9)
    for scope, ms in want["self_ms"].items():
        assert spans.scope_self_ms(ev, "jit__fused_batch", scope) == \
            pytest.approx(ms, rel=1e-9)
    # the ops' self times add up to the call's device time
    dev, = ev["devices"].values()
    (_, _, call), = dev["modules"]
    assert sum(spans._self_times(dev["ops"])) == pytest.approx(call,
                                                               rel=1e-3)
    assert spans.module_inside(ev, "jit__fused_batch", (
        "engine.wait", "engine.dispatch")) == pytest.approx(
            want["inside_wait_dispatch"], rel=1e-9)
