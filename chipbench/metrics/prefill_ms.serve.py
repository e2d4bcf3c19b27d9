"""Mean host time of `Engine.add` over the window's turns, in ms: the
fork, the copy of a shared open page, and the prefill that returns the
turn's first token (the benchmark's span; host clock)."""


def read(run):
    d = run.add_s
    return 1e3 * sum(d) / len(d) if d else None
