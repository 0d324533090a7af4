"""From a JAX profiler trace to device busy time, per-program device time
and idle gaps attributed to what the host was doing.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote into plain
lists (one per device plane and one for the host's annotations), which is
also the form of the recorded fixture the tests check ``reduce`` on.
``reduce`` works on those lists alone.
"""
from __future__ import annotations

import bisect
import glob
import os
import re

HOST_SPANS = ("generate", "submit", "pump", "search_call", "window")
# XLA's cross-device ops, by the start of their op name (with the async
# forms' ``-start`` and ``-done`` halves)
COLLECTIVES = re.compile(r"%?(all-reduce|all-gather|reduce-scatter|"
                         r"collective-permute|all-to-all)")


def load(trace_dir: str):
    """The newest ``.xplane.pb`` under ``trace_dir`` as ``ProfileData``."""
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(max(files, key=os.path.getmtime))


def extract(profile) -> dict:
    """``{"devices": {plane: {"modules": [[name, start_ns, dur_ns]], "ops":
    [...]}}, "host": [[name, start_ns, dur_ns]]}``: the device planes'
    XLA module and op events, and the host's spans named in
    ``HOST_SPANS``."""
    out = {"devices": {}, "host": []}
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {"XLA Modules": "modules", "XLA Ops": "ops"}.get(
                    line.name)
                if key:
                    dev[key] += [[e.name, e.start_ns, e.duration_ns]
                                 for e in line.events]
            if dev["modules"] or dev["ops"]:
                out["devices"][plane.name] = dev
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["host"] += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events
                                if e.name in HOST_SPANS]
    return out


def module_name(name: str) -> str:
    """``jit__fused_batch(17)`` -> ``jit__fused_batch``."""
    return re.sub(r"\(.*\)$", "", name).strip()


def op_name(name: str) -> str:
    """``fusion.123`` -> ``fusion``."""
    return re.sub(r"[.:]\d+$", "", name.split(" ")[0])


def _union(intervals, t0: float, t1: float) -> list:
    """Merged [start, end] intervals clipped to [t0, t1]."""
    merged: list = []
    for s, e in sorted((max(s, t0), min(e, t1)) for s, e in intervals):
        if e <= s:
            continue
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def window_of(events: dict) -> tuple[float, float]:
    """The traced window: the host's ``window`` span."""
    spans = [(s, s + d) for name, s, d in events["host"] if name == "window"]
    if not spans:
        raise ValueError("the trace holds no 'window' span")
    return min(s for s, _ in spans), max(e for _, e in spans)


def reduce(events: dict, top: int = 10) -> dict:
    """Device busy and idle time over the traced window, device time per
    XLA module (seconds and event count, averaged over devices), the
    device time of the collective ops inside each module's calls
    (``collectives``, averaged over devices, over the same calls as
    ``modules``), the device ops that took most time, and idle time by the
    host span that overlapped each idle gap most (``other`` where none
    did)."""
    t0, t1 = window_of(events)
    window_s = (t1 - t0) / 1e9
    devs = events["devices"]
    if not devs:
        raise ValueError("the trace holds no device plane")
    busy, modules, ops, collectives = [], {}, {}, {}
    gaps: list = []
    for dev in devs.values():
        evs = dev["ops"] or dev["modules"]
        u = _union([(s, s + d) for _, s, d in evs], t0, t1)
        busy.append(sum(e - s for s, e in u) / 1e9)
        edges = [t0] + [x for iv in u for x in iv] + [t1]
        gaps += [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                 if edges[i + 1] > edges[i]]
        for name, s, d in dev["modules"]:
            if t0 <= s and s + d <= t1:
                m = modules.setdefault(module_name(name), [0.0, 0])
                m[0] += d / 1e9 / len(devs)
                m[1] += 1 / len(devs)
        calls = sorted((s, s + d, module_name(name))
                       for name, s, d in dev["modules"]
                       if t0 <= s and s + d <= t1)
        starts = [s for s, _, _ in calls]
        for name, s, d in dev["ops"]:
            if not COLLECTIVES.match(name):
                continue
            i = bisect.bisect_right(starts, s) - 1
            if i >= 0 and s + d <= calls[i][1]:
                key = calls[i][2]
                collectives[key] = (collectives.get(key, 0.0)
                                    + d / 1e9 / len(devs))
        for name, s, d in dev["ops"]:
            lo, hi = max(s, t0), min(s + d, t1)
            if hi > lo:
                k = op_name(name)
                ops[k] = ops.get(k, 0.0) + (hi - lo) / 1e9 / len(devs)
    host = [(n, s, s + d) for n, s, d in events["host"] if n != "window"]
    idle: dict = {}
    for gs, ge in gaps:
        best, who = 0.0, "other"
        for n, s, e in host:
            ov = min(e, ge) - max(s, gs)
            if ov > best:
                best, who = ov, n
        idle[who] = idle.get(who, 0.0) + (ge - gs) / 1e9 / len(devs)
    busy_s = sum(busy) / len(busy)
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "modules": modules,
        "collectives": collectives,
        "device_ops": sorted(([k, v] for k, v in ops.items()),
                             key=lambda kv: -kv[1])[:top],
        "idle_gaps": sorted(([k, v] for k, v in idle.items()),
                            key=lambda kv: -kv[1])[:top],
    }


def module_time(summary: dict, prefix: str) -> tuple[float, float]:
    """(device seconds, calls) of the XLA modules whose name starts with
    ``prefix``, e.g. ``jit__fused_batch``."""
    s = c = 0.0
    for name, (secs, count) in summary["modules"].items():
        if name.startswith(prefix):
            s += secs
            c += count
    return s, c
