"""Device time of one call of the engine's decode step, in ms, averaged
over the calls inside the window.  The engine's prefill and decode are
both `jit__unknown` in the trace (each a `jax.jit` of a
`functools.partial`); the decode step is the program in which the
paged-attention kernel runs."""

KERNEL = r'custom_call_target="tpu_custom_call"'


def read(run):
    seconds, calls = run.trace.seconds_of_programs_running(KERNEL)
    return 1e3 * seconds / calls if calls else None
