"""Device time of one call of the jitted train step (the program named
`jit_train_step` in the trace's `XLA Modules`), in ms, averaged over the
calls inside the window."""

PROGRAM = r"^jit_train_step$"


def read(run):
    seconds, calls = run.trace.module_seconds(PROGRAM)
    return 1e3 * seconds / calls if calls else None
