"""Find a cell's knee: the highest offered rate it sustains without a
growing backlog. One process, one set-up, one window per rate.

    python3 bench/sweep.py --workload qwen32b.agent --seed 5 \
        --rates 1,2,3,4 --seconds 20

Prints one line per rate: the tails, tokens per second, the backlog at
the close (requests due in the window still without a first token) and
the requests in flight as the window opens and closes -- a backlog that
grows through the window marks a rate above the knee. The cell file
then fixes its rate at about four fifths of the knee.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from bench.run import arguments, prepare  # noqa: E402


def main(argv=None) -> int:
    ap = arguments(argparse.ArgumentParser(description=__doc__))
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    found = prepare(args)
    if found is None:
        return 2
    cell, devices, _, _ = found
    from bench import drive, harness, stats
    sys_ = harness.build(cell, seed=args.seed, devices=devices,
                         rehearse=args.rehearse)
    for rate in (float(r) for r in args.rates.split(",")):
        sys_.server.reset()
        tr = harness.offer(sys_, rate, args.seconds, args.seed)
        start = drive.clock()
        open_t = start + float(sys_.mix["lead_in_s"])
        log = drive.serve(sys_.server, tr, start=start, open_t=open_t,
                          close_t=open_t + args.seconds,
                          compiles=sys_.compiles)
        due = stats.due_in_window(log)
        row = {"rate_per_s": rate,
               "ttft_p95_ms": 1e3 * (stats.p95(stats.ttft_samples(log)) or 0),
               "itl_p95_ms": 1e3 * (stats.p95(stats.itl_samples(log)) or 0),
               "turn_p95_ms": 1e3 * (stats.p95(stats.turn_samples(log)) or 0),
               "output_tok_per_s": stats.tokens_in_window(log)
               / args.seconds,
               "due": len(due),
               "backlog": sum(1 for r in due if r.first_t is None),
               "in_flight_open": stats.in_flight(log, log.open_t),
               "in_flight_close": stats.in_flight(log, log.close_t),
               "in_flight_every_5s": [
                   stats.in_flight(log, start + harness.FLIGHT_EVERY_S * k)
                   for k in range(int((log.close_t - start)
                                      / harness.FLIGHT_EVERY_S) + 1)],
               "compiles_in_window": log.compiles_in_window,
               **stats.summary(log)}
        print("sweep", json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
