#!/usr/bin/env python3
"""Find an open-loop cell's knee: the highest rate it sustains.

    python3 bench/sweep.py --workload <name> --rates 1,2,4 --seconds 20 --seed 5

Runs the cell's open loop once per rate in one process (the programs
compile once), untraced, and prints one JSON line per rate: the latency
percentiles, the generator's lateness, and how the latency of the last
quarter of the requests compares with the first quarter's (a ratio well
above 1 means the backlog grew through the window).  The cell's rate is
then set by hand to 0.8 × the knee in ``bench/cells/<workload>.json``.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    import numpy as np

    from bench.harness import cells, runner

    base = cells.resolve(cells.load_benchmark(), args.workload)
    runner.use_compile_cache(ROOT)
    compiles = runner.CompileCounter()
    for i, rate in enumerate(float(r) for r in args.rates.split(",")):
        cell = dataclasses.replace(
            base, traffic={**base.traffic, "rate_rps": rate},
            end_to_end=[{"name": n, "unit": "ms"} for n in
                        ("latency_p50_ms", "latency_p90_ms")])
        seen = []
        res = runner.run_cell(cell, args.seed + i, args.seconds, False,
                              t_start=time.perf_counter(), compiles=compiles,
                              on_requests=seen.extend)
        lat = [1e3 * (r.done_s - r.due_s) for r in seen if r.completed]
        q = max(1, len(lat) // 4)
        print(json.dumps({
            "rate_rps": rate, "requests": len(seen),
            "p50_ms": res["metrics"]["latency_p50_ms"]["value"],
            "p90_ms": res["metrics"]["latency_p90_ms"]["value"],
            "last_over_first_quarter": float(np.median(lat[-q:])
                                             / np.median(lat[:q])),
            "lateness_p90_ms": res["window"]["lateness_p90_ms"],
            "compiles": res["window"]["compiles"],
            "correct": res["correct"],
            "service_ms_mean": float(np.mean(
                [1e3 * (r.done_s - r.sent_s) for r in seen if r.completed])),
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
