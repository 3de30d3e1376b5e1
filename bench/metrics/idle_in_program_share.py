"""Share of the traced window in which the busiest device was idle while
the innermost open program span was one of the program's own (any but
``batcher.clock``, the benchmark's clock called from the batcher): the
idle time the program's host work leaves."""

from bench import trace


def read(run):
    t = run.trace
    if not (t.ops and t.program_spans) or t.window_s <= 0:
        return None
    idle = trace.idle_by_span(t)
    mine = sum(s for name, s in idle.items()
               if name and name != "batcher.clock")
    return 100.0 * mine / t.window_s
