"""95th percentile of every gap between consecutive output tokens of a
request, over the gaps that end in the window (tokens are stamped when
``step()`` returns)."""
from bench import stats


def read(run):
    v = stats.p95(stats.itl_samples(run.log))
    return None if v is None else 1e3 * v
