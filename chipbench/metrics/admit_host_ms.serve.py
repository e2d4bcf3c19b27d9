"""Mean host time of a turn's admission, in ms: the program's span
`engine.add` (fork, page allocation, copy of a shared open page, the
prefill's dispatch) less its `engine.prefill.wait` (the wait for the
first token), over the last as many admissions as the window's turns.
Nothing where the program records fewer."""
from chipbench import spans


def read(run):
    adds = spans.last("engine.add", len(run.add_s))
    if adds is None:
        return None
    return 1e3 * sum(spans.less(adds, "engine.prefill.wait")) / len(adds)
