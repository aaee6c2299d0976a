#!/usr/bin/env python3
"""Readings that set a cell's limits: the program on many seeds, and the
control on a few, in one process.

    python3 bench/control.py --workload <name> --seeds 101-112 \
        --control-seeds 201-203 --seconds 8

For each program seed, one run of the cell (untraced, the window
``--seconds`` long) prints the numbers it compares (``checks``) and
whether it was correct.  For each control seed, the same run with the
plain reference, computed one precision lower (bfloat16 operands,
``bench.harness.reference.control_operand``), in the program's
place: it has to come out not correct.  The last line summarizes the
largest reading of the program and the smallest of the control for each
number.  The benchmark's own runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def seeds(text: str):
    lo, _, hi = text.partition("-")
    return range(int(lo), int(hi or lo) + 1)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
    from bench.harness import cells, runner
    from bench.harness.server import ControlServer

    cell = cells.resolve(cells.load_benchmark(), args.workload)
    runner.use_compile_cache(ROOT)
    compiles = runner.CompileCounter()
    worst = {"program": {}, "control": {}}
    for side, seed_list in (("program", seeds(args.seeds)),
                            ("control", seeds(args.control_seeds))):
        factory = (lambda: ControlServer(cell.config)) if side == "control" else None
        for seed in seed_list:
            res = runner.run_cell(cell, seed, args.seconds, False,
                                  t_start=time.perf_counter(),
                                  compiles=compiles, server_factory=factory)
            print(json.dumps({"side": side, "seed": seed,
                              "correct": res["correct"],
                              "attempted": res["attempted"],
                              "compared": res["window"]["compared"],
                              "checks": res["checks"]}), flush=True)
            pick = max if side == "program" else min
            for name, c in res["checks"].items():
                old = worst[side].get(name)
                worst[side][name] = (c["value"] if old is None
                                     else pick(old, c["value"]))
    print(json.dumps({"summary": {"program_largest": worst["program"],
                                  "control_smallest": worst["control"]}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
