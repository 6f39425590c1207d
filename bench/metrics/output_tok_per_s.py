"""Output tokens served in the window over the window's seconds."""
from bench import stats


def read(run):
    return stats.tokens_in_window(run.log) / (run.log.close_t
                                              - run.log.open_t)
