"""Share of the traced window in which no operation ran on the device,
in %, while the closed loop of sessions is served: the host's work
between the engine's programs."""


def read(run):
    return 100.0 * run.trace.idle_share
