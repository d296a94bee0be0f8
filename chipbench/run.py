"""Run one cell of the benchmark once.

    python chipbench/run.py --workload h2o-4b.chat-poisson --seed 7 \\
        --seconds 51 --trace 0

Resolves the cell by its name in ``BENCHMARK.json``, builds the served
stack on the chip, measures for ``--seconds`` seconds and prints, as the
last line of standard output, one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1`` a
``breakdown``, and last ``checks``, each number compared beside its
limit.  The same numbers end standard error.  With no TPU, or fewer chips
than the cell asks for, it exits with code 2 and prints no result.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness  # noqa: E402

T_PROCESS = harness.process_start()


def main(argv=None) -> int:
    import argparse
    import json

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative integer")

    cell = harness.resolve(args.workload)
    from repro.launch.serve import use_compile_cache
    use_compile_cache()
    try:
        out = harness.run_cell(cell, args.seed, args.seconds,
                               bool(args.trace), t_process=T_PROCESS)
    except harness.NoChip as e:
        print(f"no chip: {e}", file=sys.stderr)
        return 2
    for line in out.notes:
        print(line, file=sys.stderr)
    for name, c in out.result["checks"].items():
        print(f"check {name}: " + " ".join(f"{k}={v}" for k, v in c.items()),
              file=sys.stderr)
    print(json.dumps(out.result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
