"""Engine: mean host wall time of the ``step()`` calls in the traced
window that only decoded (no admission, no prefill chunk). The step ends
in host syncs, so this is blocked time."""


def read(run):
    steps = run.window_steps(decode_only=True)
    if not steps:
        return None
    return 1e3 * sum(s.t1 - s.t0 for s in steps) / len(steps)
