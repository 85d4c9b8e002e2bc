"""Sinkhorn-WMD retrieval benchmark: one run of one cell on the chip.

    python bench/run.py --workload paper_5k.full_bulk --seed 7 \
        --seconds 30 --trace 0

Builds the cell's deployment from the seed (``BENCHMARK.json`` names its
configuration and traffic files), warms the shapes its traffic uses,
drives the service for ``--seconds``, then checks a seeded sample of the
answers against the plain reference in `wmdbench.reference`. The last
line of stdout is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the end-to-end metrics, or with ``--trace 1`` the per-layer
ones, read from a profiler trace of the window), ``device``, with
``--trace 1`` ``breakdown``, and last ``checks``, each number compared with
its limit. The same numbers end standard error.

It runs on a TPU only: with no TPU, fewer chips than the cell asks for, a
device kind missing from ``bench/peaks.json``, or no ``src/repro`` beside
``bench/``, it exits non-zero and prints no result. The persistent
compilation cache is ``.jax_cache/`` in the checkout.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

BENCH = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(ROOT, ".jax_cache")


def fail(msg: str) -> int:
    print(f"bench/run.py: {msg}", file=sys.stderr, flush=True)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(ROOT, "src", "repro")):
        return fail("src/repro not found beside bench/; run from a "
                    "checkout of the repository")
    sys.path.insert(0, os.path.join(ROOT, "src"))
    sys.path.insert(0, BENCH)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from wmdbench import spec
    try:
        bm = spec.load_benchmark(ROOT)
        entry = spec.cell(bm, args.workload)
    except (OSError, spec.SpecError) as e:
        return fail(str(e))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu":
        return fail(f"no TPU: jax.devices()[0] is a {devices[0].platform!r} "
                    f"device; this benchmark measures a TPU only")
    if len(devices) < int(entry["chips"]):
        return fail(f"{args.workload} needs {entry['chips']} chips, "
                    f"found {len(devices)}")
    try:
        peaks = spec.peaks(devices[0].device_kind)
    except spec.SpecError as e:
        return fail(str(e))
    from repro.serving import enable_compilation_cache
    enable_compilation_cache(CACHE_DIR)
    from wmdbench import cell, report
    r = cell.run(bm, args.workload, seed=args.seed, seconds=args.seconds,
                 trace=bool(args.trace), devices=devices, t_start=T_START)
    r["ctx"].peaks = peaks
    line = report.result_line(bm, args.workload, r, bool(args.trace),
                              devices)
    report.emit(line, report.log_lines(args.workload, args.seed, r))
    return 0


if __name__ == "__main__":
    sys.exit(main())
