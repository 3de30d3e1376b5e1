"""90th percentile of due time to first token over every request due in
the window (one that never produced a token counts as infinitely late).
About 122 requests fall due in a 51 s window of the chat cell, so twelve
lie beyond the p90."""

from bench import stats


def read(run):
    ttft = stats.ttfts(run.records, run.window_s)
    return stats.percentile(ttft, 90) if ttft else None
