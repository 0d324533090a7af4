"""The control of ``correct``, and the program's own readings, for one
cell over many seeds in one process (set-up is paid once; later runs find
every program in memory).

    python3 bench/control.py --workload <name> --seeds 1,2,3 --seconds 4

For each seed it runs the cell's window at the cell's own load and prints
one JSON line: the program's compared numbers, and the control's on the
same served requests: the reference in the program's place, computed one
precision below the configuration's (the decision transformer with float8
matmul operands, the cost model in bfloat16).  The limits in the
configuration's file are set from these readings (PERF.md).  Needs the
chips the cell asks for, like ``run.py``.
"""
import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import pathlib  # noqa: E402
import sys  # noqa: E402

sys.path[0] = str(pathlib.Path(__file__).resolve().parent.parent)


def readings(spec, driver, seed: int, seconds: float, compiles) -> dict:
    from bench import check, harness
    R = spec.reference
    _, numbers, rec = driver.run(spec, seed, seconds, False,
                                 harness.now(), compiles)
    limits = spec.config["limits"]
    if spec.mix["loop"] == "search":
        control = check.check_search(R, rec.sample, limits, dt=R.BF16)
    else:
        control = check.control_served(R, rec.sample, rec.params,
                                       rec.model, limits)
    return {"seed": seed, "program": numbers, "control": control}


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    args = ap.parse_args()
    from bench import harness, search, serve
    spec = harness.Spec(args.workload)
    harness.require_chips(spec.chips)
    harness.use_cache()
    compiles = harness.Compiles()
    driver = search if spec.mix["loop"] == "search" else serve
    for seed in (int(s) for s in args.seeds.split(",")):
        r = readings(spec, driver, seed, args.seconds, compiles)
        print(json.dumps(r, default=float), flush=True)


if __name__ == "__main__":
    main()
