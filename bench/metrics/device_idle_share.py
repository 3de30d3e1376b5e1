"""Share of the traced window in which no operation ran on the device,
averaged over the devices used."""

from bench import trace


def read(run):
    if not run.trace.ops or run.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - trace.mean_busy_s(run.trace) / run.trace.window_s)
