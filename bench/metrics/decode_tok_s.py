"""Output tokens that reached the host inside the window, per second of
the window."""

from bench import stats


def read(run):
    return stats.window_tokens(run.records, run.window_s) / run.window_s
