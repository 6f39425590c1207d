"""Readings for the limit of ``correct``: the program's widest logit
gap and the int8 control's, seed by seed, at the cell's own size and
load. One process, so the set-up's compiles are paid once.

    python3 bench/calibrate.py --workload qwen32b.agent --seed 1000 \
        --seeds 12 --seconds 10

Each seed builds its weights and traffic, serves a short window, frees
the program's state and runs the harness's comparison
(bench/correct.py) once with the control in the program's place: its
line names the model module whose reference judged it (``model``) and
gives the control's verdict (``correct``, which has to be false) and
gap (``logit_gap``), and the program's own gap over the same sample
(``program_gap``). The limit in the configuration file is set
between the program's largest reading and the control's smallest.
"""
import argparse
import gc
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
from bench.run import arguments, prepare  # noqa: E402


def readings(cell, devices, seed: int, seconds: float, rehearse: bool):
    """One seed: the control's verdict, with the program's gap beside
    it (``correct.judge`` with ``control``)."""
    from bench import correct, drive, harness
    sys_ = harness.build(cell, seed=seed, devices=devices,
                         rehearse=rehearse)
    tr = harness.offer(sys_, float(cell.cell["rate_per_s"]), seconds, seed)
    start = drive.clock()
    open_t = start + float(sys_.mix["lead_in_s"])
    log = drive.serve(sys_.server, tr, start=start, open_t=open_t,
                      close_t=open_t + seconds, compiles=sys_.compiles)
    sys_.server.close()
    gc.collect()
    verdict = correct.judge(
        cell.model, sys_.weights, sys_.shape, log.reqs, seed,
        sys_.conf["engine"]["cache_len"],
        float(sys_.conf["correct"]["logit_gap_limit"]), control=True)
    return verdict


def main(argv=None) -> int:
    ap = arguments(argparse.ArgumentParser(description=__doc__))
    ap.add_argument("--seeds", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)
    found = prepare(args)
    if found is None:
        return 2
    cell, devices, _, root = found
    model = os.path.relpath(cell.model.__file__, root)
    for k in range(args.seeds):
        v = readings(cell, devices, args.seed + k, args.seconds,
                     args.rehearse)
        print("calibrate", json.dumps({"seed": args.seed + k,
                                       "model": model, **v}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
