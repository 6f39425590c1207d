"""Median of due time to first token, over every request due in the
window; one still without a first token at the close counts at the
age it had reached."""
import numpy as np

from bench import stats


def read(run):
    v = stats.ttft_samples(run.log)
    return 1e3 * float(np.median(v)) if v else None
