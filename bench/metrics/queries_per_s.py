"""Expression calls completed in the timed window over its whole length."""


def read(run):
    calls = run.timed(("expression",))
    return len(calls) / run.window_s if calls and run.window_s > 0 else None
