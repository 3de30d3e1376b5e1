"""Mean device time of one execution of the engine's prefill program
(``jit__prefill``, one admission wave) in the traced part of the window."""

from bench import trace


def read(run):
    times = trace.program_times(run.trace, "jit__prefill")
    return 1e3 * sum(times) / len(times) if times else None
