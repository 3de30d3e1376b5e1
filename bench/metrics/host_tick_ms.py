"""Mean host time of one pass of the program's batcher loop (span
``batcher.tick``) less its wait for the decode step's tokens
(``serve.sync``) and its calls into the benchmark's clock
(``batcher.clock``): the program's own host work per tick, in the traced
part of the window."""

from bench import trace


def read(run):
    ticks = trace.span_times(run.trace, "batcher.tick",
                             less=("serve.sync", "batcher.clock"))
    return 1e3 * sum(ticks) / len(ticks) if ticks else None
