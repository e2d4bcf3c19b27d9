"""Mean seconds of `CheckpointManager.restore` in the window's resumes:
fetching every shard from WTF and decoding it to host arrays (the
benchmark's span around the call; host clock)."""


def read(run):
    d = run.restore_s
    return sum(d) / len(d) if d else None
