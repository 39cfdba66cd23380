"""The merge_join_count kernel's share of its roofline, in %: the least
time to read both sorted int32 key columns once at the chip's HBM
bandwidth over the device time the trace gives it."""


def read(run):
    return run.roofline("merge_join_count")
