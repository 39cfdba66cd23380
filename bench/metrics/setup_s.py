"""Seconds from process start to the opening of the timed window: data
generation, loading, warm-up and any compilation."""


def read(run):
    return run.setup_s
