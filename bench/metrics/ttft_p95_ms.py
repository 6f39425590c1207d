"""95th percentile of due time to first token, over every request due
in the window; one still without a first token at the close counts at
the age it had reached, so a stall shows."""
from bench import stats


def read(run):
    v = stats.p95(stats.ttft_samples(run.log))
    return None if v is None else 1e3 * v
