"""The filter_count kernel's share of its roofline, in %: the least time
its logical work needs at the chip's HBM bandwidth (predicate columns x
rows x 4 bytes) over the device time the trace gives it."""


def read(run):
    return run.roofline("filter_count")
