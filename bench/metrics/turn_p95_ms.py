"""95th percentile of due time to last token -- what the agent loop
waits on, since a tool call runs once the plan is whole -- over every
request due in the window; one unfinished at the close counts at its
age."""
from bench import stats


def read(run):
    v = stats.p95(stats.turn_samples(run.log))
    return None if v is None else 1e3 * v
