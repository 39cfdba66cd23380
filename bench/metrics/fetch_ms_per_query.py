"""Result delivery per expression call, in ms: the program's
``session.fetch`` spans (the device-to-host copy of the answer and its
compaction on the host) per ``session.execute`` span, over the traced
window."""
from bench.harness import phases


def read(run):
    return phases.ms_per_execute(run.events, ("session.fetch",))
