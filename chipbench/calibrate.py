"""Readings that a cell's correctness limit is set from.

    python chipbench/calibrate.py --workload h2o-4b.chat-poisson \\
        --seeds 11 12 13 --seconds 51 --controls int8

For each seed, in one process: one run of the cell as ``run.py`` makes it
(fresh weights from the seed, the window at the cell's own load), then
the comparison of the sampled requests with the plain reference, and of
each control: the reference in a lower precision, put in the program's
place.  Prints one JSON line per seed with the widest gaps.  The
benchmark's own runs never run a control.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness  # noqa: E402


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", nargs="*", default=["int8"])
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    from repro.launch.serve import use_compile_cache
    use_compile_cache()
    for seed in args.seeds:
        out = harness.run_cell(cell, seed, args.seconds, False,
                               controls=tuple(args.controls))
        for line in out.notes:
            print(line, file=sys.stderr)
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "correct": out.result["correct"],
                          "metrics": out.result["metrics"],
                          "checks": out.result["checks"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
