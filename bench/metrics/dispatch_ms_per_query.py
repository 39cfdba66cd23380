"""Dispatch time per expression call, in ms: the program's
``session.execute.gather`` and ``session.execute.dispatch`` spans (its
inputs gathered, its jitted program called until the call returns) per
``session.execute`` span, over the traced window."""
from bench.harness import phases


def read(run):
    return phases.ms_per_execute(
        run.events, ("session.execute.gather", "session.execute.dispatch"))
