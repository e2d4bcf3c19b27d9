"""Mean host time of a decode step, in ms: the program's span
`engine.step` (tokens, positions, copy-on-write checks, page table, the
decode's dispatch) less its `engine.step.wait` (the wait for the step's
tokens), over the last as many steps that decoded a batch as the
window's steps.  Nothing where the program records fewer."""
from chipbench import spans


def read(run):
    steps = spans.last("engine.step", len(run.steps),
                       where=lambda r: r.attrs.get("batch"))
    if steps is None:
        return None
    return 1e3 * sum(spans.less(steps, "engine.step.wait")) / len(steps)
