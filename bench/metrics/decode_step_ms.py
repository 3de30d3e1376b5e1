"""Mean device time of one execution of the engine's decode program
(``jit_serve_step``) in the traced part of the window."""

from bench import trace


def read(run):
    times = trace.program_times(run.trace, "jit_serve_step")
    return 1e3 * sum(times) / len(times) if times else None
