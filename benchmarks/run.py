"""Benchmark aggregator — one function per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run            # full sweep
    PYTHONPATH=src python benchmarks/run.py --smoke    # CI sanity leg

Emits the paper-figure tables (fig2 / fig3a-c) and the protocol bench's
``name,us_per_call,derived`` CSV pairs (CPU host clock; the speed of the
served path is measured on the chip by ``bench/run.py``).  ``--smoke``
runs only the fast protocol correctness leg (fused, survivor-decode,
batched-engine and autotuned-session paths at reduced m, plus quick
``autotune_*`` pairs appended to ``BENCH_PROTOCOL.json``) so CI catches
regressions in the new paths without paying for the full sweep.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, "src")
# make `import benchmarks` work under direct-script invocation too
sys.path.insert(0, os.path.abspath(os.path.join(os.path.dirname(__file__), "..")))


def main(argv=None) -> None:
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument("--smoke", action="store_true",
                        help="fast protocol sanity leg only (CI)")
    parser.add_argument("--seed", type=int, default=0,
                        help="rng seed threaded through the smoke leg and "
                             "fleet replays, so recorded numbers are "
                             "reproducible run to run")
    parser.add_argument("--sim-divergence", action="store_true",
                        help="predicted-vs-replayed divergence gate "
                             "(DESIGN.md §11): tune + replay two specs on "
                             "a 1000-device simulated fleet; non-zero exit "
                             "when the makespan ratio drifts past "
                             "tolerance or the placement ranking flips")
    args = parser.parse_args(argv)

    if args.sim_divergence:
        import json

        from repro.sim import gate

        print("== sim divergence gate (predicted vs replayed) ==")
        report = gate(seed=args.seed)
        print(json.dumps(report.describe(), indent=1))
        if not report.ok:
            sys.exit("sim divergence gate FAILED: cost-model predictions "
                     "drifted past tolerance or the tuned-vs-oblivious "
                     "ranking flipped")
        print("sim divergence gate OK")
        return

    from benchmarks import (  # noqa: WPS433
        fig2_workers,
        fig3_overheads,
        protocol_bench,
    )

    if args.smoke:
        print("== protocol smoke (fused / survivor / engine) ==")
        protocol_bench.smoke(seed=args.seed)
        return

    print("== fig2: required workers (paper Fig. 2) ==")
    fig2_workers.main()
    print("== fig3: storage/computation/communication (paper Fig. 3) ==")
    fig3_overheads.main()
    print("== protocol end-to-end ==")
    protocol_bench.main()


if __name__ == "__main__":
    main()
