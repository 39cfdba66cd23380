"""Share of the traced window in which no operation ran on the device, in
%: 1 minus the union of the device operations' intervals over the window."""


def read(run):
    return run.idle_share()
