"""Share of the traced window in which no operation ran on the device,
in %, while the job resumes again and again."""


def read(run):
    return 100.0 * run.trace.idle_share
