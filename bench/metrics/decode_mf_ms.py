"""Device time of one execution of the engine's decode program
(``jit_serve_step``) in operations under the program's ``mf_projection``
scope (every multiplication-free projection, whichever CIM route it
takes), in the traced part of the window."""

from bench import trace


def read(run):
    times = trace.scope_times(run.trace, "jit_serve_step")
    return 1e3 * trace.under(times, "mf_projection") or None
