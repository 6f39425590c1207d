"""The comparison fails its control: the reference computed in int8
(the step below the configuration's bfloat16) in the program's place,
judged by the harness's own comparison over the sequences the program
served, at smoke size on the CPU."""
import argparse

from repro.common import perf


def test_int8_control_fails_and_program_passes():
    from bench import calibrate, run
    args = argparse.Namespace(workload="qwen32b.long", seed=21,
                              benchmark="BENCHMARK.json", rehearse=True)
    cell, devices, _, _ = run.prepare(args)
    flags = perf.get_flags()
    try:
        v = calibrate.readings(cell, devices, 21, 3.0, rehearse=True)
    finally:
        perf.set_flags(flags)
    assert v["tokens_checked"] >= 10
    assert v["program_gap"] <= v["limit"] < v["logit_gap"]
    assert v["correct"] is False
