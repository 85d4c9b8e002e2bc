"""The readings that the limits of ``correct`` are set from.

    python bench/control.py --workload paper_5k.full_bulk --seconds 10 \
        --seeds 1 2 3 4 5 6 7 8 9 10 11 12 --control-seeds 13 14 15

Runs the cell as `bench/run.py` does, in one process, once per program
seed and once per control seed, and prints each run's compared numbers
(one JSON line each), then the largest program reading and the smallest
control reading of every number. The control is the plain reference,
computed in bfloat16 (a precision step below the configurations' float32),
put in the program's place (`wmdbench.cell.control_answers`). The
benchmark's own runs never run it. TPU only, like `bench/run.py`.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("bench/control.py: no TPU", file=sys.stderr)
        return 1
    from repro.serving import enable_compilation_cache
    enable_compilation_cache(os.path.join(ROOT, ".jax_cache"))
    from wmdbench import cell, spec
    bm = spec.load_benchmark(ROOT)
    worst: dict = {}
    runs = [(s, False) for s in args.seeds] + \
        [(s, True) for s in args.control_seeds]
    for seed, control in runs:
        t0 = time.perf_counter()
        r = cell.run(bm, args.workload, seed=seed, seconds=args.seconds,
                     trace=False, devices=devices, t_start=t0,
                     answer=cell.control_answers if control else None)
        kind = "control" if control else "program"
        print(json.dumps({
            "kind": kind, "seed": seed, "correct": r["correct"],
            "attempted": r["attempted"], "failed": r["failed"],
            "checked": r["checked"], "setup_s": r["setup_s"],
            "wall_s": time.perf_counter() - t0,
            "checks": {k: v["value"] for k, v in r["checks"].items()}}),
            flush=True)
        for name, c in r["checks"].items():
            pick = min if control else max
            key = (kind, name)
            worst[key] = pick(worst.get(key, c["value"]), c["value"])
    print(json.dumps({"workload": args.workload, "program_max": {
        n: v for (k, n), v in worst.items() if k == "program"},
        "control_min": {n: v for (k, n), v in worst.items()
                        if k == "control"}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
