"""Run a cell in this process at its smoke size on the CPU (what
``bench/run.py --rehearse`` does), and return its result line."""
import json

from repro.common import perf


def run_cell(capsys, workload, seed=5, seconds=2.0, benchmark=None):
    from bench import run
    argv = ["--workload", workload, "--seed", str(seed), "--seconds",
            str(seconds), "--trace", "0", "--rehearse"]
    if benchmark is not None:
        argv += ["--benchmark", str(benchmark)]
    flags = perf.get_flags()
    try:
        rc = run.main(argv)
    finally:
        perf.set_flags(flags)          # rehearsals shrink the prefill chunk
    out = capsys.readouterr().out.strip().splitlines()
    assert rc == 0
    return json.loads(out[-1]), out
