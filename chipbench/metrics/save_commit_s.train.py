"""Seconds from a save's submission to its commit, as the program's
`AsyncCheckpointer.durations` records them; the mean over the window's
saves.  Nothing where no save was made."""


def read(run):
    d = run.save_commit_s
    return sum(d) / len(d) if d else None
