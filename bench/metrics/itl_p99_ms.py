"""99th percentile of every gap between consecutive output tokens of one
request, pooled over all requests, for gaps that end in the window."""

from bench import stats


def read(run):
    gaps = stats.itl_gaps(run.records, run.window_s)
    return 1e3 * stats.percentile(gaps, 99) if gaps else None
