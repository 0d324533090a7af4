"""Find the highest rate an open-loop serving cell sustains, once, by a
sweep on the chip; the cell's mix then fixes its rate at about four
fifths of it (PERF.md).

    python3 bench/knee.py --workload dt-zipf-open --rates 1000,2000,4000

One process: set-up is paid once, later runs find every program in
memory.  Prints one JSON line per rate: p95 latency, generator lateness,
refused requests and the queue left at the window's close.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path[0] = str(pathlib.Path(__file__).resolve().parent.parent)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seed", type=int, default=2 ** 31 + 99)
    args = ap.parse_args()
    import numpy as np
    from bench import harness, serve
    spec = harness.Spec(args.workload)
    harness.require_chips(spec.chips)
    harness.use_cache()
    compiles = harness.Compiles()
    base = spec.mix
    for rate in (float(r) for r in args.rates.split(",")):
        spec.mix = dict(base, rate_rps=rate)
        e2e, numbers, rec = serve.run(spec, args.seed, args.seconds, False,
                                      harness.now(), compiles)
        lat = np.asarray(rec.latencies_s)
        sched = rec.stats1.get("scheduler", {})
        print(json.dumps({
            "rate_rps": rate, "attempted": rec.attempted,
            "refused": rec.failed, "p50_ms": float(np.median(lat)) * 1e3,
            "p95_ms": e2e["map_p95_ms"],
            "p99_ms": float(np.quantile(lat, 0.99)) * 1e3,
            "gen_late_p95_ms": float(np.quantile(rec.gen_late_s, 0.95)) * 1e3,
            "max_queue_depth": sched.get("max_queue_depth"),
            "numbers": numbers}, default=float), flush=True)


if __name__ == "__main__":
    main()
