"""Front-end time per expression call, in ms: the harness's call span
minus the program's ``repro/session.query`` spans inside it, averaged over
the traced calls, on the profiler trace's one clock. Left out where the
program puts no ``session.query`` span in the trace."""
from bench.harness import phases


def read(run):
    return phases.frontend_ms_per_query(run.events)
