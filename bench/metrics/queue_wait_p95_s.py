"""95th percentile of due time to admission by the batcher, over every
request due in the window."""

from bench import stats


def read(run):
    waits = stats.queue_waits(run.records, run.window_s)
    return stats.percentile(waits, 95) if waits else None
