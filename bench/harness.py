"""What every cell's run shares: reading ``BENCHMARK.json`` and the files
it names, the cell's serving settings and plain reference, the chip check,
compile accounting, the weights, the profiler window, the per-layer metric
readers and the result line."""
from __future__ import annotations

import importlib.util
import json
import pathlib
import re
import shutil
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent.parent
BENCH = ROOT / "bench"
TRACE_DIR = ROOT / ".bench_trace"
REFERENCE = "bench/reference.py"   # a configuration's reference by default
TRACE_START = 0.3      # share of the window before the traced stretch
TRACE_SECONDS = 1.0    # length of the traced stretch
HEAD_BIAS = 0.3        # encoded action at the centre of the head's outputs
HEAD_SPREAD = 0.4      # their spread
BASE_KEY = 0           # the key of the one mapper that seeds reorder


def log(msg: str) -> None:
    print(msg, flush=True)


def load_module(path: pathlib.Path):
    """The Python file at ``path``, loaded once per process."""
    name = "bench_file_" + re.sub(r"\W", "_", str(path.relative_to(ROOT)))
    if name not in sys.modules:
        spec = importlib.util.spec_from_file_location(name, path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        sys.modules[name] = mod
    return sys.modules[name]


def load_reference(path: str):
    """The plain reference module a configuration names: a file under
    ``bench/``, given relative to the checkout's root."""
    file = (ROOT / path).resolve()
    if BENCH not in file.parents or not file.is_file():
        raise SystemExit(f"bench: reference {path!r} is no file under "
                         f"bench/")
    return load_module(file)


class Spec:
    """One cell of ``BENCHMARK.json`` with its configuration, its mix and
    the plain reference its configuration names (``reference``, by default
    ``bench/reference.py``)."""

    def __init__(self, workload: str):
        from . import generate
        self.bench = json.loads((ROOT / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in self.bench["workloads"]}
        if workload not in cells:
            raise SystemExit(f"unknown workload {workload!r}; have "
                             f"{sorted(cells)}")
        self.cell = cells[workload]
        configs = {c["name"]: c for c in self.bench["configs"]}
        self.config = json.loads(
            (ROOT / configs[self.cell["config"]]["file"]).read_text())
        self.mix = generate.load(self.cell["traffic"])
        self.chips = int(self.cell["chips"])
        self.reference = load_reference(self.config.get("reference",
                                                        REFERENCE))

    def serving(self) -> dict:
        """The cell's serving settings.  The configuration's ``serving``
        block describes the deployment on one chip; a cell of n > 1 chips
        runs n data-parallel replicas of it behind one engine
        (``replicas`` n, ``max_coalesce`` n times the configuration's:
        each chip takes as many lanes a tick as one chip alone)."""
        sc = dict(self.config["serving"])
        if self.chips > 1:
            sc.update(replicas=self.chips,
                      max_coalesce=sc["max_coalesce"] * self.chips)
        return sc

    def end_to_end(self) -> list[dict]:
        return [m for m in self.bench["end_to_end"]
                if self.cell["name"] in m.get("workloads",
                                              [self.cell["name"]])]

    def per_layer(self) -> list[dict]:
        mine = {m["name"] for m in self.end_to_end()}
        return [m for m in self.bench["per_layer"]
                if (self.cell["name"] in m["workloads"] if "workloads" in m
                    else m["moves"] in mine)]


class Compiles:
    """Backend compile events and persistent-cache hits, from JAX's
    monitoring events (a program read from the cache is both)."""

    def __init__(self):
        import jax
        self.events = 0
        self.seconds = 0.0
        self.hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event: str, secs: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.events += 1
            self.seconds += secs

    def _event(self, event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def snapshot(self) -> tuple:
        return self.events, self.seconds, self.hits


def require_chips(n: int):
    """The first n TPU devices; exits non-zero without a result where
    JAX's devices are no TPU or fewer than n."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        sys.exit(f"bench: no TPU found (JAX's first device is "
                 f"{devs[0].platform!r}); the benchmark runs only on a TPU")
    if len(devs) < n:
        sys.exit(f"bench: the cell needs {n} TPU chips, found {len(devs)}")
    return devs[:n]


def use_cache() -> str:
    """JAX's persistent compilation cache at the program's fixed place
    (``JAX_COMPILATION_CACHE_DIR`` where set, else ``.jax_cache`` in the
    checkout), caching every program however fast it compiled."""
    import jax
    sys.path.insert(0, str(ROOT / "src"))
    from repro.compile_cache import use_compile_cache
    where = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return where


def peaks(kind: str) -> dict:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         f"bench/peaks.json")
    return table[kind]


def device_info(devs) -> dict:
    mem = 0
    for d in devs:
        stats = d.memory_stats() or {}
        mem = max(mem, int(stats.get("peak_bytes_in_use", 0)))
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "memory_peak_bytes": mem}


def dt_shapes(model: dict) -> dict:
    """The decision transformer's parameter tree as (shape, kind) leaves,
    in the layout the program reads."""
    d, dff = model["d_model"], model["d_ff"]

    def dense(i, o, bias=True):
        p = {"w": ((i, o), "w")}
        if bias:
            p["b"] = ((o,), "b")
        return p

    def norm():
        return {"g": ((d,), "g"), "b": ((d,), "b")}

    return {
        "emb_r": dense(1, d), "emb_s": dense(8, d), "emb_a": dense(1, d),
        "emb_h": dense(model["hw_dim"], d),
        "time": {"emb": ((model["max_steps"], d), "emb")},
        "type": {"emb": ((3, d), "emb")},
        "ln_f": norm(),
        "head": {"w": ((d, 1), "head_w"), "b": ((1,), "head_b")},
        "blocks": [{"ln1": norm(), "ln2": norm(),
                    "attn": {k: dense(d, d, bias=False)
                             for k in ("q", "k", "v", "o")},
                    "mlp": {"up": dense(d, dff), "down": dense(dff, d)}}
                   for _ in range(model["n_blocks"])],
    }


def dt_weights(model: dict, seed: int):
    """Weights from the seed, made on the device in one jitted call.

    One mapper, drawn from the fixed key ``BASE_KEY``: dense kernels
    N(0, 1/fan_in), embeddings N(0, 0.02^2), and biases and norm offsets
    N(0, 0.02^2) so that every term of the forward pass is exercised.  The
    action head is centred: its bias is HEAD_BIAS and its kernel
    N(0, HEAD_SPREAD^2/fan_in), so that the mapper proposes micro-batches
    as well as syncs and the guard does its work.  The seed then reorders
    the mapper's units: the residual features, each block's heads, the
    features within each head (queries with keys, values with the output
    projection) and each block's feed-forward units.  Every seed's weights
    differ and compute the same function, up to the rounding of a sum
    taken in another order, so every seed gives the guard and the repair
    the same work; left to chance, one seed's mapper can do three times
    the guard work of another's."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    is_leaf = lambda x: (isinstance(x, tuple) and len(x) == 2
                         and isinstance(x[1], str))
    leaves, treedef = jax.tree_util.tree_flatten(dt_shapes(model),
                                                 is_leaf=is_leaf)
    d, dff, heads = model["d_model"], model["d_ff"], model["n_heads"]
    hd = d // heads

    def init(key):
        keys = jax.random.split(key, len(leaves))
        out = []
        for k, (shape, kind) in zip(keys, leaves):
            z = jax.random.normal(k, shape, jnp.float32)
            if kind == "head_w":
                out.append(HEAD_SPREAD * z / np.sqrt(shape[0]))
            elif kind == "head_b":
                out.append(jnp.full(shape, HEAD_BIAS, jnp.float32))
            elif kind == "w":
                out.append(z / np.sqrt(shape[0]))
            elif kind == "g":
                out.append(1.0 + 0.02 * z)
            else:
                out.append(0.02 * z)
        return jax.tree_util.tree_unflatten(treedef, out)

    def head_columns(order, key):
        """Columns of a [d, d] projection: the heads in ``order``, and the
        features of each head in an order drawn from ``key``."""
        within = jax.vmap(lambda k: jax.random.permutation(k, hd))(
            jax.random.split(key, heads))
        return (order[:, None] * hd + within).reshape(d)

    def reorder(p, key):
        kr, kb = jax.random.split(key)
        r = jax.random.permutation(kr, d)         # residual features

        def out_d(lin):                           # output width d
            lin = dict(lin, w=lin["w"][:, r])
            if "b" in lin:
                lin["b"] = lin["b"][r]
            return lin

        norm = lambda n: {"g": n["g"][r], "b": n["b"][r]}
        q = dict(p)
        for name in ("emb_r", "emb_s", "emb_a", "emb_h"):
            q[name] = out_d(p[name])
        q["time"] = {"emb": p["time"]["emb"][:, r]}
        q["type"] = {"emb": p["type"]["emb"][:, r]}
        q["ln_f"] = norm(p["ln_f"])
        q["head"] = dict(p["head"], w=p["head"]["w"][r, :])
        blocks = []
        for blk, k in zip(p["blocks"], jax.random.split(kb,
                                                        len(p["blocks"]))):
            kh, kqk, kvo, kff = jax.random.split(k, 4)
            order = jax.random.permutation(kh, heads)
            cqk, cvo = head_columns(order, kqk), head_columns(order, kvo)
            f = jax.random.permutation(kff, dff)
            a, m = blk["attn"], blk["mlp"]
            blocks.append({
                "ln1": norm(blk["ln1"]), "ln2": norm(blk["ln2"]),
                "attn": {"q": {"w": a["q"]["w"][r][:, cqk]},
                         "k": {"w": a["k"]["w"][r][:, cqk]},
                         "v": {"w": a["v"]["w"][r][:, cvo]},
                         "o": {"w": a["o"]["w"][cvo][:, r]}},
                "mlp": {"up": {"w": m["up"]["w"][r][:, f],
                               "b": m["up"]["b"][f]},
                        "down": {"w": m["down"]["w"][f][:, r],
                                 "b": m["down"]["b"][r]}}})
        q["blocks"] = blocks
        return q

    key = jax.random.PRNGKey(int(np.random.default_rng([seed, 1])
                                 .integers(2 ** 31)))
    return jax.block_until_ready(jax.jit(
        lambda k: reorder(init(jax.random.PRNGKey(BASE_KEY)), k))(key))


class Tracer:
    """The profiler over a short steady stretch of the window, with the
    stretch itself as the host span ``window``."""

    def __init__(self, enabled: bool, seconds: float):
        self.enabled = enabled
        self.start_at = TRACE_START * seconds
        self.stop_at = self.start_at + min(TRACE_SECONDS, 0.5 * seconds)
        self.state = "idle"
        self._span = None

    def tick(self, elapsed: float) -> None:
        import jax
        if not self.enabled:
            return
        if self.state == "idle" and elapsed >= self.start_at:
            shutil.rmtree(TRACE_DIR, ignore_errors=True)
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0       # the spans are enough
            jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
            self._span = jax.profiler.TraceAnnotation("window")
            self._span.__enter__()
            self.state = "on"
        elif self.state == "on" and elapsed >= self.stop_at:
            self.stop()

    def stop(self) -> None:
        import jax
        if self.state == "on":
            self._span.__exit__(None, None, None)
            t = now()
            jax.profiler.stop_trace()
            log(f"profiler stop {now() - t:.3f} s")
            self.state = "done"

    def summary(self) -> dict | None:
        from . import trace
        if self.state != "done":
            return None
        t = now()
        out = trace.reduce(trace.extract(trace.load(str(TRACE_DIR))))
        log(f"trace read and reduced in {now() - t:.3f} s")
        return out


def span(name: str):
    import jax
    return jax.profiler.TraceAnnotation(name)


def read_metrics(metrics: list[dict], rec) -> dict:
    """Each metric's reader ``bench/metrics/<name>.py`` on the run's
    record; a reader that finds nothing returns None and the metric is
    left out."""
    out = {}
    for m in metrics:
        v = load_module(BENCH / "metrics" / f"{m['name']}.py").read(rec)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def check_limits(numbers: dict, limits: dict) -> tuple[bool, dict]:
    """(every compared number within its limit, {name: {value, limit}})."""
    shown = {k: {"value": int(v) if isinstance(v, int) else float(v),
                 "limit": limits[k]} for k, v in numbers.items()}
    return all(v <= limits[k] for k, v in numbers.items()), shown


def emit(result: dict, shown: dict) -> None:
    """Every compared number beside its limit on standard error, then the
    result line, with the same numbers under ``checks``, last."""
    for k, v in shown.items():
        print(f"check {k}: {v['value']!r} (limit {v['limit']!r})",
              file=sys.stderr, flush=True)
    result = dict(result, checks=shown)
    print(json.dumps(result), flush=True)


def now() -> float:
    return time.perf_counter()
