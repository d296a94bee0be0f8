"""Find a cell's knee: the highest mean arrival rate, under the cell's own
arrival process, at which a window ends with no more than one wave of
due requests not yet started.

    python chipbench/sweep.py --workload h2o-4b.code-bursty --seed 5 \\
        --seconds 51 --rates 1.5 2.0 2.5 3.0

Sets the stack up once, then runs one window per rate, each with the
traffic file's schedule at that rate; no wave starts after the window's
end.  Prints one JSON line per rate.  The rate a cell runs at is written
into its traffic file by hand, as a number, from this sweep.
"""
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

from chipbench import harness, stats, traffic as T  # noqa: E402


def backlog(reqs, window_end: float) -> int:
    """Requests due by the window's end and not started by then."""
    return sum(1 for r in reqs if r.due <= window_end
               and (r.t_enqueued is None or r.t_enqueued > window_end))


def main(argv=None) -> int:
    import argparse
    import copy
    import json

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    args = ap.parse_args(argv)
    cell = harness.resolve(args.workload)
    from repro.launch.serve import use_compile_cache
    use_compile_cache()
    scheds = []
    for rate in args.rates:
        mix = copy.deepcopy(cell.traffic)
        mix["arrivals"]["rate_per_s"] = rate
        mix.pop("requests", None)            # the whole window's arrivals
        scheds.append(T.schedule(mix, args.seconds))
    stack = harness.Stack(cell.config, cell.chips, args.seed,
                          [r.prompt_len for s in scheds for r in s],
                          annotate=False)
    try:
        for rate, sched in zip(args.rates, scheds):
            prompts = T.prompts(sched, stack.cfg.vocab_size, args.seed)
            reqs, t0, end = stack.serve(sched, prompts,
                                        stop_after=args.seconds)
            done = [r for r in reqs if r.token_times]
            row = {"workload": args.workload, "rate_per_s": rate,
                   "requests": len(reqs), "served": len(done),
                   "backlog_at_window_end": backlog(reqs, t0 + args.seconds),
                   "batch": stack.batch, "waves": stack.waves,
                   "wave_rows": [b for _, b in stack.wave_log],
                   "wave_starts_s": [round(t, 3) for t, _ in stack.wave_log]}
            if done:
                row.update(stats.end_to_end(done, t0, end))
            print(json.dumps(row), flush=True)
    finally:
        stack.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
