"""Share of the traced window in which no operation ran on the device,
in % (1 - busy / window, busy being the union of the device's operation
intervals)."""


def read(run):
    return 100.0 * run.trace.idle_share
