"""Host planning time per expression call: ``session.execute`` minus
``session.execute.run`` (which ends in ``block_until_ready``), averaged
over the calls in the traced window, in ms."""


def read(run):
    return run.host_ms_per_execute()
