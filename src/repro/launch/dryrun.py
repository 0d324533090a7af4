import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# The two lines above MUST run before any other import (jax locks the device
# count at first init) — this file is the only place that forces 512 host
# devices; tests and benches see the real device count.

# Multi-pod dry-run: lower + compile every (arch x shape) cell on the
# production meshes and extract the roofline terms (assignment: MULTI-POD
# DRY-RUN + ROOFLINE ANALYSIS).
#
# Usage:
#   PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3_1b --shape train_4k
#   PYTHONPATH=src python -m repro.launch.dryrun --all [--both-meshes]
#
# Per cell this prints/saves: memory_analysis (proves it fits), cost_analysis
# FLOPs/bytes, parsed collective bytes, the three roofline terms and the
# dominant bottleneck.  Results are cached as JSON per cell.

import argparse
import json
import pathlib
import time
import traceback


def _compile_variant(cfg, shape, mesh, impl, remat):
    import jax
    from repro.launch import steps
    with jax.set_mesh(mesh):
        if shape.kind == "train":
            jitted, args = steps.build_train_step(cfg, shape, mesh,
                                                  impl=impl, remat=remat)
        elif shape.kind == "prefill":
            jitted, args = steps.build_prefill(cfg, shape, mesh, impl=impl)
        else:
            jitted, args = steps.build_decode_step(cfg, shape, mesh,
                                                   impl=impl)
        return jitted.lower(*args).compile()


def _costs(compiled, hlo_analysis):
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    return (float(cost.get("flops", 0.0)),
            float(cost.get("bytes accessed", 0.0)),
            hlo_analysis.collective_bytes(compiled.as_text()))


def lower_cell(arch: str, shape_name: str, *, multi_pod: bool = False,
               impl: str = "xla", remat: str = "full",
               donate: bool = True) -> dict:
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.configs import get_config, SHAPES
    from repro.launch.mesh import make_production_mesh
    from repro.launch import steps, hlo_analysis

    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_dev = mesh.devices.size
    t0 = time.perf_counter()

    compiled = _compile_variant(cfg, shape, mesh, impl, remat)
    t_compile = time.perf_counter() - t0
    t_lower = 0.0

    mem = compiled.memory_analysis()
    mem_d = {k: float(getattr(mem, k, 0) or 0) for k in
             ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "generated_code_size_in_bytes",
              "alias_size_in_bytes")}

    # XLA's cost analysis counts a while-loop (the layer scan) body ONCE,
    # so per-step flops/bytes/collectives would be ~L x undercounted.  We
    # compile L=1 and L=2 variants of the cell (cheap) and extrapolate:
    # total = intercept + per_layer * L.
    def with_layers(n):
        kw = {"n_layers": n}
        if cfg.family == "encdec":
            kw["encoder_layers"] = n
        return dataclasses.replace(cfg, **kw)

    from repro.nn import flags
    with flags.force_unroll():
        f1, b1, x1 = _costs(_compile_variant(with_layers(1), shape, mesh,
                                             impl, remat), hlo_analysis)
        f2, b2, x2 = _costs(_compile_variant(with_layers(2), shape, mesh,
                                             impl, remat), hlo_analysis)
    L = cfg.n_layers
    flops = max(f1 + (f2 - f1) * (L - 1), 0.0)
    byts = max(b1 + (b2 - b1) * (L - 1), 0.0)
    coll = {k: max(x1[k] + (x2[k] - x1[k]) * (L - 1), 0.0) for k in x1}

    # analytic MODEL_FLOPS: 6*N_active*D for train, 2*N_active*D otherwise
    n_active = cfg.active_param_count()
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 6.0 * n_active * tokens
    elif shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        model_flops = 2.0 * n_active * tokens
    else:
        tokens = shape.global_batch          # one token per sequence
        model_flops = 2.0 * n_active * tokens

    rep = hlo_analysis.roofline_terms(flops=flops, bytes_accessed=byts,
                                      coll_bytes=coll["total"],
                                      n_devices=n_dev,
                                      model_flops=model_flops)
    return {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "n_devices": n_dev, "kind": shape.kind, "impl": impl, "remat": remat,
        "ok": True,
        "t_lower_s": round(t_lower, 2), "t_compile_s": round(t_compile, 2),
        "memory": mem_d, "flops_per_device": flops,
        "bytes_per_device": byts, "collectives": coll,
        "roofline": rep.as_dict(),
    }


def run_cell(arch, shape_name, multi_pod, out_dir, force=False, **kw):
    tag = f"{arch}__{shape_name}__{'2x16x16' if multi_pod else '16x16'}"
    path = out_dir / f"{tag}.json"
    if path.exists() and not force:
        rec = json.loads(path.read_text())
        if rec.get("ok"):
            print(f"[cached] {tag}: "
                  f"{rec.get('roofline', {}).get('bottleneck')}")
            return rec
        # cached failure: retry (the bug may be fixed)
    try:
        rec = lower_cell(arch, shape_name, multi_pod=multi_pod, **kw)
    except Exception as e:  # a failure here is a bug in the system
        rec = {"arch": arch, "shape": shape_name,
               "mesh": "2x16x16" if multi_pod else "16x16", "ok": False,
               "error": f"{type(e).__name__}: {e}",
               "trace": traceback.format_exc()[-2000:]}
    path.write_text(json.dumps(rec, indent=1))
    if rec["ok"]:
        r = rec["roofline"]
        print(f"[ok] {tag}: compile {rec['t_compile_s']}s "
              f"temp {rec['memory']['temp_size_in_bytes']/2**30:.2f} GiB/dev "
              f"terms c/m/x = {r['t_compute']*1e3:.2f}/{r['t_memory']*1e3:.2f}"
              f"/{r['t_collective']*1e3:.2f} ms -> {r['bottleneck']}")
    else:
        print(f"[FAIL] {tag}: {rec['error']}")
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--impl", default="xla")
    ap.add_argument("--remat", default="full")
    ap.add_argument("--out", default="artifacts/dryrun")
    args = ap.parse_args()

    out_dir = pathlib.Path(args.out)
    out_dir.mkdir(parents=True, exist_ok=True)

    from repro.configs import cells
    todo = []
    if args.all:
        for a, s, ok, why in cells(include_skipped=False):
            todo.append((a, s))
    else:
        assert args.arch and args.shape, "--arch and --shape (or --all)"
        todo = [(args.arch, args.shape)]

    meshes = [args.multi_pod] if not args.both_meshes else [False, True]
    n_fail = 0
    for a, s in todo:
        for mp in meshes:
            rec = run_cell(a, s, mp, out_dir, force=args.force,
                           impl=args.impl, remat=args.remat)
            n_fail += 0 if rec.get("ok") else 1
    print(f"done: {len(todo) * len(meshes)} cells, {n_fail} failures")
    raise SystemExit(1 if n_fail else 0)


if __name__ == "__main__":
    main()
