"""Model step: model FLOPs of the decode-only steps in the traced window
over their wall time times the chips' bf16 peak. FLOPs come from the
configuration's shapes and the live lengths (bench/flops.py)."""
from bench import flops


def read(run):
    steps = run.window_steps(decode_only=True)
    if not steps or run.peaks is None:
        return None
    work = sum(flops.decode_token_flops(run.shape, kv)
               for s in steps for kv in s.kv_lens)
    wall = sum(s.t1 - s.t0 for s in steps)
    return 100.0 * work / (wall * run.peaks["bf16_flops_per_s"]
                           * run.n_chips)
