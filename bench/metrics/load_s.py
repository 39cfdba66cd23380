"""Seconds of ``create_dataset`` per dataset loaded, each ended once every
array of the dataset is on the device; the sum over the datasets."""


def read(run):
    spans = run.spans.get("load", [])
    return sum(spans) if spans else None
