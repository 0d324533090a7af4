"""The program's own spans and name scopes in a traced run.

The program marks its host work with ``jax.profiler.TraceAnnotation`` spans
(``scheduler.*``, ``engine.*``, ``gsampler.*``, see ``src/repro/obs.py``)
and its device programs with ``jax.named_scope`` (``dt_decode``, ``guard``,
``env_step``, ``evaluate_grid``, ``repair``).  This module re-reads the
``.xplane.pb`` that the traced stretch left in ``harness.TRACE_DIR`` and
gives, over the benchmark's ``window`` span:

- host spans by name, with their metadata;
- the self time of device ops (an op's time less that of the ops nested in
  it, as a ``while`` holds its body) by name-scope path, inside whole calls
  of one XLA module;
- the device's idle time, attributed to the innermost host span open at
  each instant.

``extract`` turns the profile into plain lists, the form of the recorded
fixture ``bench/tests/trace_spans.json``; the rest works on those lists.
A program that has no such spans or scopes gives empty results, and the
metric readers then return None.
"""
from __future__ import annotations

import bisect
import glob
import os

from . import trace

OWNERS = ("scheduler", "engine", "gsampler")     # the program's span owners
SCOPE_STAT = "tf_op"      # the op's metadata stat that holds its scope path


def is_program_span(name: str) -> bool:
    return name.split(".", 1)[0] in OWNERS


# The fields of the profiler's ``XSpace`` protobuf (tsl/profiler/protobuf/
# xplane.proto) that are read here, by their field numbers.  JAX's
# ``ProfileData`` gives no op's metadata stats, where the TPU keeps the
# scope, so the file is parsed with this partial schema; other fields are
# skipped.  Maps are read as their repeated key/value entries, and text as
# bytes.
_SCHEMA = {
    "XSpace": [("planes", 1, "XPlane")],
    "XPlane": [("name", 2, "bytes"), ("lines", 3, "XLine"),
               ("event_metadata", 4, "EventMetadataEntry"),
               ("stat_metadata", 5, "StatMetadataEntry")],
    "XLine": [("name", 2, "bytes"), ("timestamp_ns", 3, "int64"),
              ("events", 4, "XEvent")],
    "XEvent": [("metadata_id", 1, "int64"), ("offset_ps", 2, "int64"),
               ("duration_ps", 3, "int64"), ("stats", 4, "XStat")],
    "EventMetadataEntry": [("key", 1, "int64"), ("value", 2,
                                                 "XEventMetadata")],
    "XEventMetadata": [("name", 2, "bytes"), ("stats", 5, "XStat")],
    "StatMetadataEntry": [("key", 1, "int64"), ("value", 2,
                                                "XStatMetadata")],
    "XStatMetadata": [("name", 2, "bytes")],
    "XStat": [("metadata_id", 1, "int64"), ("double_value", 2, "double"),
              ("uint64_value", 3, "uint64"), ("int64_value", 4, "int64"),
              ("str_value", 5, "bytes"), ("ref_value", 7, "uint64")],
}


def _xspace():
    """The ``XSpace`` message class of ``_SCHEMA``."""
    from google.protobuf import (descriptor_pb2, descriptor_pool,
                                 message_factory)
    F = descriptor_pb2.FieldDescriptorProto
    scalar = {"bytes": F.TYPE_BYTES, "int64": F.TYPE_INT64,
              "uint64": F.TYPE_UINT64, "double": F.TYPE_DOUBLE}
    fdp = descriptor_pb2.FileDescriptorProto(
        name="bench_xplane.proto", package="bench_xplane", syntax="proto3")
    for msg, fields in _SCHEMA.items():
        m = fdp.message_type.add(name=msg)
        for name, number, kind in fields:
            f = m.field.add(name=name, number=number)
            if kind in scalar:
                f.type, f.label = scalar[kind], F.LABEL_OPTIONAL
            else:                    # a map entry's value is singular
                f.type, f.label = F.TYPE_MESSAGE, (
                    F.LABEL_OPTIONAL if name == "value" else F.LABEL_REPEATED)
                f.type_name = f".bench_xplane.{kind}"
    pool = descriptor_pool.DescriptorPool()
    pool.Add(fdp)
    return message_factory.GetMessageClass(
        pool.FindMessageTypeByName("bench_xplane.XSpace"))


def _text(b: bytes) -> str:
    return b.decode("utf-8", "replace")


def _stats(stats, names: dict) -> dict:
    out = {}
    for st in stats:
        if st.str_value:
            v = _text(st.str_value)
        elif st.ref_value:
            v = names.get(st.ref_value, "")
        elif st.int64_value:
            v = st.int64_value
        elif st.uint64_value:
            v = st.uint64_value
        else:
            v = st.double_value
        out[names.get(st.metadata_id, "")] = v
    return out


def extract(path: str) -> dict:
    """``{"host": [[name, start_ns, dur_ns, {metadata}]], "devices":
    {plane: {"modules": [[name, start_ns, dur_ns]], "ops": [[name,
    start_ns, dur_ns, scope]]}}}`` from the ``.xplane.pb`` at ``path``: the
    host's program and benchmark spans, and the device planes' XLA modules
    and ops, each op with the scope path of its metadata stat
    ``SCOPE_STAT`` (empty where it has none)."""
    space = _xspace()()
    with open(path, "rb") as f:
        space.ParseFromString(f.read())
    out: dict = {"host": [], "devices": {}}
    for plane in space.planes:
        pname = _text(plane.name)
        names = {e.key: _text(e.value.name) for e in plane.stat_metadata}
        device = pname.startswith("/device:")
        if not (device or pname.startswith("/host:")):
            continue
        meta = {}
        for e in plane.event_metadata:
            name = _text(e.value.name)
            meta[e.key] = (name, str(_stats(e.value.stats, names).get(
                SCOPE_STAT, "")) if device else "")
        dev = {"modules": [], "ops": []}
        for line in plane.lines:
            lname, t0 = _text(line.name), line.timestamp_ns
            for ev in line.events:
                name, scope = meta.get(ev.metadata_id, ("", ""))
                start, dur = t0 + ev.offset_ps / 1e3, ev.duration_ps / 1e3
                if device and lname == "XLA Modules":
                    dev["modules"].append([name, start, dur])
                elif device and lname == "XLA Ops":
                    dev["ops"].append([name.split(" ")[0], start, dur,
                                       scope])
                elif not device and (is_program_span(name)
                                     or name in trace.HOST_SPANS):
                    out["host"].append([name, start, dur,
                                        _stats(ev.stats, names)])
        if dev["modules"] or dev["ops"]:
            out["devices"][pname] = dev
    return out


def events(rec) -> dict | None:
    """The run's span events, read once from ``harness.TRACE_DIR`` and kept
    on the record; None where the run left no trace."""
    if getattr(rec, "span_events", None) is None:
        from .harness import TRACE_DIR
        try:
            rec.span_events = extract(_newest(str(TRACE_DIR)))
        except FileNotFoundError:
            return None
    return rec.span_events


def _newest(trace_dir: str) -> str:
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(files, key=os.path.getmtime)


def window(ev: dict) -> tuple[float, float]:
    return trace.window_of({"host": [h[:3] for h in ev["host"]]})


def host_spans(ev: dict, prefix: str = "") -> list:
    """``[name, start, end, metadata]`` of the program's spans that lie
    wholly inside the window and whose name starts with ``prefix``."""
    t0, t1 = window(ev)
    return [[n, s, s + d, m] for n, s, d, m in ev["host"]
            if is_program_span(n) and n.startswith(prefix)
            and t0 <= s and s + d <= t1]


def _self_times(ops: list) -> list:
    """Each op's duration less the durations of the ops directly nested in
    it (ops on one device line nest by time)."""
    order = sorted(range(len(ops)), key=lambda i: (ops[i][1], -ops[i][2]))
    own = [float(op[2]) for op in ops]
    stack: list = []
    for i in order:
        s, e = ops[i][1], ops[i][1] + ops[i][2]
        while stack and ops[stack[-1]][1] + ops[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= ops[stack[-1]][1] + ops[stack[-1]][2]:
            own[stack[-1]] -= ops[i][2]
        stack.append(i)
    return own


def scope_self_ms(ev: dict, module: str, scope: str) -> float | None:
    """Self time, in ms per call, of the device ops under name scope
    ``scope`` (a component of the op's scope path) inside the whole calls
    of the XLA modules named ``module`` in the window, averaged over
    devices; None where no such call or no op under the scope is found."""
    t0, t1 = window(ev)
    total, calls, found = 0.0, 0.0, False
    for dev in ev["devices"].values():
        spans = sorted((s, s + d) for n, s, d in dev["modules"]
                       if trace.module_name(n) == module
                       and t0 <= s and s + d <= t1)
        if not spans:
            continue
        calls += len(spans) / len(ev["devices"])
        starts = [s for s, _ in spans]
        ops = dev["ops"]
        for op, own in zip(ops, _self_times(ops)):
            if scope not in op[3].split("/"):
                continue
            k = bisect.bisect_right(starts, op[1]) - 1
            if k >= 0 and op[1] + op[2] <= spans[k][1]:
                total += own / len(ev["devices"])
                found = True
    return total / calls / 1e6 if calls and found else None


def _innermost(spans: list, t0: float, t1: float) -> list:
    """``[start, end, name]`` segments covering [t0, t1], each named for
    the innermost span open over it (``None`` where none is)."""
    spans = sorted(spans, key=lambda x: (x[1], -x[2]))
    points = sorted({t0, t1, *(p for _, s, e in spans for p in (s, e)
                               if t0 < p < t1)})
    segs, stack, j = [], [], 0
    for a, b in zip(points, points[1:]):
        while j < len(spans) and spans[j][1] <= a:
            stack.append(spans[j])
            j += 1
        stack = [x for x in stack if x[2] > a]
        segs.append([a, b, stack[-1][0] if stack else None])
    return segs


def idle_by_span(ev: dict) -> dict:
    """Device idle seconds in the window by the innermost host span open
    (program or benchmark span other than ``window``; ``None`` where no
    span is open), averaged over devices."""
    t0, t1 = window(ev)
    host = [(n, s, s + d) for n, s, d, _ in ev["host"] if n != "window"]
    segs = _innermost(host, t0, t1)
    out: dict = {}
    for dev in ev["devices"].values():
        busy = trace._union([(s, s + d) for _, s, d, *_ in
                             (dev["ops"] or dev["modules"])], t0, t1)
        edges = [t0] + [x for iv in busy for x in iv] + [t1]
        gaps = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        k = 0
        for gs, ge in gaps:
            while segs[k][1] <= gs:
                k += 1
            j = k
            while j < len(segs) and segs[j][0] < ge:
                a, b, who = segs[j]
                ov = min(b, ge) - max(a, gs)
                if ov > 0:
                    out[who] = out.get(who, 0.0) + ov / 1e9 / len(
                        ev["devices"])
                j += 1
    return out


def module_inside(ev: dict, module: str, prefixes: tuple) -> float | None:
    """Share of the device time of ``module`` calls in the window that lies
    inside host spans whose names start with one of ``prefixes``."""
    t0, t1 = window(ev)
    spans = trace._union([(s, e) for n, s, e, _ in host_spans(ev)
                          if n.startswith(prefixes)], t0, t1)
    total = inside = 0.0
    for dev in ev["devices"].values():
        for n, s, d in dev["modules"]:
            if trace.module_name(n) != module:
                continue
            lo, hi = max(s, t0), min(s + d, t1)
            if hi <= lo:
                continue
            total += hi - lo
            inside += sum(max(0.0, min(hi, e) - max(lo, a))
                          for a, e in spans)
    return inside / total if total else None


def _at(stats, *keys):
    for k in keys:
        if not isinstance(stats, dict) or k not in stats:
            return None
        stats = stats[k]
    return stats


def delta(rec, *keys) -> float | None:
    """A counter of the engine's ``stats()`` over the window: the value at
    ``keys`` in ``rec.stats1`` less that in ``rec.stats0``; None where the
    program does not keep it."""
    v0, v1 = (_at(getattr(rec, k, None), *keys) for k in ("stats0", "stats1"))
    return None if v0 is None or v1 is None else float(v1) - float(v0)


def span_seconds(rec, owner: tuple, name: str) -> float | None:
    """Host seconds of span ``name`` over the window, from the span tallies
    at ``owner`` in ``stats()`` (``()`` for the engine, ``("scheduler",)``
    for its scheduler); 0 for a span not entered, None where the program
    keeps no tallies."""
    t0, t1 = (_at(getattr(rec, k, None), *owner, "spans")
              for k in ("stats0", "stats1"))
    if t0 is None or t1 is None:
        return None
    return (t1.get(name, {}).get("seconds", 0.0)
            - t0.get(name, {}).get("seconds", 0.0))
