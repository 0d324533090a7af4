"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  Exits non-zero, with no result line, unless
JAX's devices are TPUs and as many as the cell's ``chips``.  Set-up
(weights from the seed, the warmed programs, the traffic) runs from
process start to the first timed request and is ``setup_s``; then the
window runs for ``--seconds``, and afterwards what it served is compared
with the plain reference.  With ``--trace 1`` a short steady stretch of
the window is traced and the result carries the cell's per-layer metrics
instead of its end-to-end ones.  The last stdout line is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics``, ``device``, with
``--trace 1`` also ``breakdown``, and last ``checks``: each compared number
with its limit, which also end standard error.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path[0] = str(pathlib.Path(__file__).resolve().parent.parent)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()

    from bench import harness
    spec = harness.Spec(args.workload)
    devs = harness.require_chips(spec.chips)
    harness.log(f"device: {devs[0].platform} {devs[0].device_kind} x "
                f"{len(devs)}; compile cache {harness.use_cache()}")
    compiles = harness.Compiles()
    from bench import search, serve
    driver = search if spec.mix["loop"] == "search" else serve
    e2e, numbers, rec = driver.run(spec, args.seed, args.seconds,
                                   bool(args.trace), T_PROCESS, compiles)
    events, secs, hits = compiles.snapshot()
    harness.log(f"compile events in all {events} ({secs:.3f} s), "
                f"persistent-cache hits {hits}; setup_s {e2e['setup_s']:.3f}")
    correct, shown = harness.check_limits(numbers, spec.config["limits"])
    rec.peaks = harness.peaks(rec.device["kind"])
    result = {"correct": correct, "attempted": rec.attempted,
              "failed": rec.failed, "device": rec.device}
    if args.trace:
        if rec.trace is None:
            sys.exit("bench: the traced stretch did not complete")
        result["metrics"] = harness.read_metrics(spec.per_layer(), rec)
        result["device"] = dict(rec.device, busy_s=rec.trace["busy_s"],
                                window_s=rec.trace["window_s"])
        result["breakdown"] = {"device_ops": rec.trace["device_ops"],
                               "idle_gaps": rec.trace["idle_gaps"]}
    else:
        result["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                         "unit": m["unit"]}
                             for m in spec.end_to_end()}
    harness.emit(result, shown)


if __name__ == "__main__":
    main()
